import os
import sys

# Tests run on the single real CPU device; kernels run in interpret mode.
# (The sharded-fleet test sets XLA_FLAGS for 4 host devices only in its
# own subprocess, test_fleet_engine.py.)
os.environ.setdefault("REPRO_KERNELS", "interpret")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

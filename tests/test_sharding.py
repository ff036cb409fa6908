"""Sharding rules: logical->spec mapping, divisibility pruning, and a
small-mesh lower+compile in a subprocess (8 host devices)."""
import json
import subprocess
import sys
import textwrap

import pytest

from jax.sharding import PartitionSpec as P


class FakeMesh:
    def __init__(self, names, shape):
        self.axis_names = names
        import numpy as np
        self.devices = np.zeros(shape)


def spec(axes, mesh, rules="baseline", dims=None):
    from repro.sharding.rules import RULE_SETS, logical_to_spec
    return logical_to_spec(axes, mesh, RULE_SETS[rules], dims)


def test_logical_mapping_single_pod():
    mesh = FakeMesh(("data", "model"), (16, 16))
    assert spec(("batch", "seq", "embed_act"), mesh) == P("data", None, None)
    assert spec(("embed", "mlp"), mesh) == P("data", "model")
    assert spec(("vocab", "embed"), mesh) == P("model", "data")


def test_logical_mapping_multi_pod():
    mesh = FakeMesh(("pod", "data", "model"), (2, 16, 16))
    s = spec(("batch", "seq"), mesh)
    assert s == P(("pod", "data"), None)


def test_unknown_mesh_axes_pruned():
    mesh = FakeMesh(("data", "model"), (4, 2))
    s = spec(("batch", "seq"), mesh)   # 'pod' not in mesh
    assert s == P("data", None)


def test_divisibility_pruning():
    mesh = FakeMesh(("data", "model"), (16, 16))
    # kv-head dim 8 not divisible by 16 -> replicated
    s = spec(("layers", "cache_batch", "cache_seq", "cache_heads", None),
             mesh, dims=(28, 128, 32768, 8, 128))
    assert s == P(None, "data", "model", None, None)
    # batch 1 -> batch axes dropped
    s = spec(("batch", "seq"), mesh, dims=(1, 4096))
    assert s == P(None, None)
    # batch 128 divisible by 16
    s = spec(("batch", "seq"), mesh, dims=(128, 4096))
    assert s == P("data", None)


def test_tuple_axes_partial_prune():
    mesh = FakeMesh(("pod", "data", "model"), (2, 16, 16))
    # batch 2: only 'pod' (size 2) fits
    s = spec(("batch",), mesh, dims=(2,))
    assert s == P("pod")


def test_zero3_rules_fully_data_parallel():
    mesh = FakeMesh(("data", "model"), (16, 16))
    # batch over every axis, activations unsharded elsewhere
    assert spec(("batch", "seq", "embed_act"), mesh, rules="zero3",
                dims=(256, 4096, 2048)) == P(("data", "model"), None, None)
    # weights 2D sharded (gathered at use under SPMD)
    assert spec(("embed", "mlp"), mesh, rules="zero3",
                dims=(2048, 6144)) == P("data", "model")


def test_moe_rules_expert_axes():
    mesh = FakeMesh(("data", "model"), (16, 16))
    # moe_ep: expert weights whole per model shard
    assert spec(("experts", "expert_embed", "expert_mlp"), mesh,
                rules="moe_ep", dims=(128, 2048, 768)) == P("model", None, None)
    # moe_ep2d: f sharded over data (TP-within-expert)
    assert spec(("experts", "expert_embed", "expert_mlp"), mesh,
                rules="moe_ep2d", dims=(128, 5120, 8192)) == \
        P("model", None, "data")


def test_all_rule_sets_have_same_keys():
    from repro.sharding.rules import RULE_SETS
    keys = {name: set(r) for name, r in RULE_SETS.items()}
    base = keys["baseline"]
    for name, k in keys.items():
        assert k == base, f"rule set {name} key mismatch: {k ^ base}"


def test_chip_peaks_by_device_kind():
    """Roofline denominators come from a table keyed by device kind; an
    unknown kind is refused, never given another chip's peaks."""
    from repro.launch.mesh import chip_peaks
    assert chip_peaks("TPU v5 lite")["peak_flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="TPU v4"):
        chip_peaks("TPU v4")


SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, sys.argv[1])
    os.environ["REPRO_KERNELS"] = "ref"
    import jax
    from repro.launch.dryrun import run_cell
    rec = run_cell(sys.argv[2], sys.argv[3], "debug", "baseline", smoke=True)
    print("RESULT " + str(rec["ok"]) + " " + rec.get("error", ""))
""")


@pytest.mark.parametrize("arch,shape", [
    ("qwen3-1.7b", "train_4k"),
    ("qwen3-moe-30b-a3b", "decode_32k"),
    ("whisper-medium", "prefill_32k"),
])
def test_small_mesh_lower_compile(arch, shape, tmp_path):
    """Sharding config must lower+compile on a small debug mesh — the
    CI-scale proxy of the 512-chip dry-run (which runs out-of-band)."""
    import os
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    prog = SUBPROCESS_PROG.replace('"debug"', '"debug"')
    out = subprocess.run(
        [sys.executable, "-c", prog, src, arch, shape],
        capture_output=True, text=True, timeout=560)
    assert "RESULT True" in out.stdout, out.stdout + out.stderr[-2000:]

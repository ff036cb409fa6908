"""Machine and workload configurations of the paper's systems
(``seth``: HPC2N's Seth, the case-study machine of Fig. 7)."""

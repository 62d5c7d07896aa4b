"""Model FLOPs of the steps done over time x chips x bf16 peak (%)."""
from chipbench.readers import flops_pct, measured_s


def read(run):
    return flops_pct(run, measured_s(run))

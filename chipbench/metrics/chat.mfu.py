"""Model FLOPs of every prompt and generated token over the device busy
time of the traced window x bf16 peak (%)."""
from chipbench.readers import flops_pct


def read(run):
    return flops_pct(run, run.trace and run.trace["busy_s"])

"""95th percentile of the gaps between consecutive output tokens of every
request, over the gaps that end in the window (ms)."""
from chipbench.readers import p95


def read(run):
    v = p95(run.samples.get("itl_s", []))
    return None if v is None else 1e3 * v

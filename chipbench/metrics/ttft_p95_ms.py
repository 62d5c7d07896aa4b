"""95th percentile over all requests due in the window of the time from
when each was due to its first token being visible to the harness (ms)."""
from chipbench.readers import p95


def read(run):
    v = p95(run.samples.get("ttft_s", []))
    return None if v is None else 1e3 * v

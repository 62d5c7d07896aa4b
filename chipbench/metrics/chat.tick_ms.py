"""Mean time of one ServeEngine.tick() (admit, prefill, insert, decode,
host argmax) (ms)."""
from chipbench.readers import mean_span_ms


def read(run):
    return mean_span_ms(run, "tick")

"""Mean time of the batch_fn call the Trainer makes each step (ms)."""
from chipbench.readers import mean_span_ms


def read(run):
    return mean_span_ms(run, "batch")

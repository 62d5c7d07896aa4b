"""Tokens of every step completed in the window over the window (tokens/s)."""


def read(run):
    lo, hi = run.window
    return run.counters["window_tokens"] / (hi - lo)

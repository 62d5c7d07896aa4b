"""Process start to window start, compiles included (s)."""


def read(run):
    return run.setup_s

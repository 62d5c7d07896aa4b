"""Device time of one run of the serve engine's batched decode program,
``serve_decode``, over the traced window (ms)."""
from chipbench import program_trace


def read(run):
    return program_trace.numbers(run.trace).get("chat.decode_device_ms")

"""Share of the traced window in which no operation ran on the device (%)."""
from chipbench.readers import idle_pct


def read(run):
    return idle_pct(run)

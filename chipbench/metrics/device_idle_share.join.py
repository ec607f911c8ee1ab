"""Device: share of the traced window in which the chip ran no operation."""
from chipbench.harness.readers import idle_share_pct


def read(run):
    return idle_share_pct(run)

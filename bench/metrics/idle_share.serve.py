"""Device: share of the traced window in which no operation ran on the
device, in percent, under open-loop serving traffic."""
from bench.metrics_common import idle_share


def read(ctx):
    return idle_share(ctx)

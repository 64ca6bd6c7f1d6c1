"""Decode lane: device milliseconds of one fused decode step, the mean over
the steps that ran in the traced window."""
from bench.metrics_common import DECODE_MODULES, module_time


def read(ctx):
    s, n = module_time(ctx, DECODE_MODULES)
    return 1e3 * s / n if n else None

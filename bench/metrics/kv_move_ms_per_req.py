"""KV transfer: device milliseconds of the KV gather and scatter programs
per request handed from prefill to decode in the window."""
from bench.metrics_common import KV_MOVE_MODULES, module_time


def read(ctx):
    s, n = module_time(ctx, KV_MOVE_MODULES)
    handed = ctx["counters"]["handed_to_decode"]
    if not n or not handed:
        return None
    return 1e3 * s / handed

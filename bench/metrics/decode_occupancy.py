"""Decode lane: mean share of the decode slots that held a request in a
step, in percent: tokens decoded for clients in the window over steps
times slots."""


def read(ctx):
    steps = ctx["counters"]["decode_steps"]
    if not steps:
        return None
    return 100.0 * len(ctx["work"]["decode_ctx"]) / (
        steps * ctx["counters"]["max_slots"])

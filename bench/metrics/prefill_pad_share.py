"""Prefill lane: share of the tokens pushed through the prefill forward
that are bucket padding, over the window, in percent."""


def read(ctx):
    c = ctx["counters"]
    total = c["padded_tokens"] + c["compute_tokens"]
    if not total:
        return None
    return 100.0 * c["padded_tokens"] / total

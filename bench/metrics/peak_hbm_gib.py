"""Device: peak bytes in use on the fullest chip, in GiB, as the runtime's
allocator reports them after the window."""


def read(ctx):
    b = ctx.get("memory_peak_bytes")
    return b / 2 ** 30 if b else None

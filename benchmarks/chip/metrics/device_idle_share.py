"""device_idle_share: 1 - (union of the device's op intervals / the traced
window), averaged over the chips, in percent."""


def read(ctx):
    if not ctx.busy_ns or ctx.hi <= ctx.lo:
        return None
    busy = sum(ctx.busy_ns) / ctx.chips
    return 100.0 * (1.0 - busy / (ctx.hi - ctx.lo))

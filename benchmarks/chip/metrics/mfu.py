"""mfu: the model FLOPs of the traced window's horizons (real samples only,
``chipbench.flops``) over the window times the chips times the chip's bf16
peak (``peaks.json``), in percent."""


def read(ctx):
    if ctx.flops <= 0 or ctx.window_s <= 0:
        return None
    peak = ctx.peak["bf16_flops_per_s"]
    return 100.0 * ctx.flops / (ctx.window_s * ctx.chips * peak)

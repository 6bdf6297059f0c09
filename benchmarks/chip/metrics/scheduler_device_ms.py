"""scheduler_device_ms: device time of the fused GWMIN greedy programs
(the whole selection while_loop under x64) per instance planned in the
traced window."""
from chipbench import xtrace

MODULES = ("_fused_single", "greedy_step", "greedy_rounds_fused",
           "_fused_sharded")


def read(ctx):
    ns = xtrace.module_ns(ctx.trace, MODULES, ctx.lo, ctx.hi)
    if ns <= 0 or ctx.instances <= 0:
        return None
    return ns * 1e-6 / ctx.instances

"""round_body_device_ms: device time of the scanned horizon programs
(SGD, quantize, aggregate and eval of every round, inside one lax.scan)
per instance-round run in the traced window, summed over the chips."""
from chipbench import xtrace

MODULES = (
    "run_horizon", "run_horizon_vmapped", "run_horizon_online",
    "run_horizon_online_vmapped", "_horizon_core", "_online_horizon_core",
    # the cell sweep's shard_map'd scan: fl_engine._sharded_horizon_fn
    # jits a function named ``fn``
    "fn",
)


def read(ctx):
    ns = xtrace.module_ns(ctx.trace, MODULES, ctx.lo, ctx.hi)
    if ns <= 0 or ctx.instance_rounds <= 0:
        return None
    return ns * 1e-6 / ctx.instance_rounds

"""round_body_us_per_token: device time of the scanned horizon programs
(the modules ``round_body_device_ms`` reads) per token position those
rounds ran through the model, trained and evaluated.

Tokens per instance-round are a constant of the cell this metric lists
(``CELL``), fixed by its configuration: K devices a round, each running
nb = ceil(its rows / B) minibatches of B rows of S tokens per local epoch
(padding included), plus S tokens for each test row, every round
evaluated.  The cell's shards are equal, so every device runs the same
nb."""
import math

from chipbench import harness, world, xtrace

CELL = "granite-h-micro.fedlora-mapel-gwmin"


def tokens_per_round(config):
    fl, data = config["fl"], config["data"]
    s = data["row_tokens"] - 1
    rows = max(world.shard_sizes(data["train_rows"], fl["num_devices"],
                                 data["size_sigma"], data["min_per_device"]))
    trained = (fl["group_size"] * math.ceil(rows / fl["batch_size"])
               * fl["batch_size"] * s * fl["local_epochs"])
    return trained + data["test_rows"] * s


def read(ctx):
    if ctx.instance_rounds <= 0:
        return None
    modules = harness.load_module(
        harness.BENCH_DIR / "metrics" / "round_body_device_ms.py").MODULES
    ns = xtrace.module_ns(ctx.trace, modules, ctx.lo, ctx.hi)
    if ns <= 0:
        return None
    tokens = tokens_per_round(harness.load_cell(CELL).config)
    return ns * 1e-3 / (ctx.instance_rounds * tokens)

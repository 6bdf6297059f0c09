"""What the benchmark takes from the program: its configuration objects,
its entry points, and the results they return, read into plain records
(the shape ``compare`` and the reference use)."""
from __future__ import annotations

import numpy as np


def fl_settings(config, traffic):
    """The configuration's FL settings with the traffic's on top."""
    return {**config["fl"], **traffic["fl"]}


def seed_sets(seed, per_call, pool=1):
    """The instance seeds of the ``pool`` calls a run rotates through, each
    ``per_call`` consecutive seeds: set j of run seed s is
    ``(b * pool + j) * per_call + i``, ``b`` the run's seed folded so that
    every instance seed stays below 2**31 (the program's PRNG keys hold 32
    bits)."""
    base = int(seed) % (2**31 // (per_call * pool))
    return [[(base * pool + j) * per_call + i for i in range(per_call)]
            for j in range(pool)]


def fl_config(fl, seed):
    from repro.config import FLConfig

    return FLConfig(**fl, seed=int(seed))


def cell_config(fl, config):
    from repro.core.channel import CellConfig

    return CellConfig(num_devices=fl["num_devices"], **config["cell"])


def record(result, seed):
    """A program ``FLResult`` as a plain record of host arrays."""
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(result.final_params)[0]
    params = {".".join(str(k.key) for k in path): np.asarray(leaf)
              for path, leaf in leaves}
    return {
        "seed": seed,
        "devices": [tuple(int(d) for d in log.devices) for log in result.logs],
        "bits": [np.asarray(log.bits, np.int64) for log in result.logs],
        "rates": [np.asarray(log.rates, np.float64) for log in result.logs],
        "times": np.asarray([log.wall_time_s for log in result.logs]),
        "accs": np.asarray([log.test_accuracy for log in result.logs]),
        "params": params,
    }


def block(results):
    """Wait for every device array the call's results hold."""
    import jax

    jax.block_until_ready([r.final_params for r in results])

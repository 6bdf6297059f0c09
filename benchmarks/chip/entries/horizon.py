"""Entry: one horizon per call through ``fl.run_federated_learning``, the
path a researcher's script takes for one simulation."""
from __future__ import annotations

from chipbench import program


class Entry:
    def __init__(self, world, config, traffic, seed):
        from repro.core import fl

        self._run = fl.run_federated_learning
        self.world = world
        self.fl = program.fl_settings(config, traffic)
        self.sets = program.seed_sets(seed, 1, traffic.get("pool", 1))
        self._cfgs = [program.fl_config(self.fl, s[0]) for s in self.sets]
        self.cell = program.cell_config(self.fl, config)

    def call(self, j):
        """Call ``j`` runs the instance seed of set ``j mod pool``."""
        cfg = self._cfgs[j % len(self.sets)]
        results = [self._run(self.world.dataset, self.world.shards,
                             self.cell, cfg)]
        program.block(results)
        return results

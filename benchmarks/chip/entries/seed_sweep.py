"""Entry: a seed sweep per call through ``fl.run_horizon_vmapped``, one
vmapped program over ``per_call`` instance seeds."""
from __future__ import annotations

from chipbench import program


class Entry:
    def __init__(self, world, config, traffic, seed):
        from repro.core import fl

        self._run = fl.run_horizon_vmapped
        self.world = world
        self.fl = program.fl_settings(config, traffic)
        self.sets = program.seed_sets(seed, traffic["per_call"],
                                      traffic.get("pool", 1))
        self._cfg = program.fl_config(self.fl, self.sets[0][0])
        self.cell = program.cell_config(self.fl, config)

    def call(self, j):
        """Call ``j`` sweeps the instance seeds of set ``j mod pool``."""
        results = self._run(self.world.dataset, self.world.shards, self.cell,
                            self._cfg, seeds=self.sets[j % len(self.sets)])
        program.block(results)
        return results

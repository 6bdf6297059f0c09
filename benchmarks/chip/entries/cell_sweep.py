"""Entry: a cells x seeds sweep per call through ``fl.run_cell_sweep``,
the cell axis sharded over the chips by ``shard_map``: each chip runs its
block of vmapped horizons."""
from __future__ import annotations

from chipbench import program


class Entry:
    def __init__(self, world, config, traffic, seed):
        from repro.core import fl

        self._run = fl.run_cell_sweep
        self.world = world
        self.fl = program.fl_settings(config, traffic)
        self.sweep = traffic["sweep"]
        per_call = self.sweep["num_cells"] * self.sweep["seeds_per_cell"]
        if per_call != traffic["per_call"]:
            raise ValueError(f"a sweep of {per_call} instances, but "
                             f"per_call is {traffic['per_call']}")
        self.sets = program.seed_sets(seed, per_call, traffic.get("pool", 1))
        self._cfgs = [program.fl_config(self.fl, s[0]) for s in self.sets]
        self.cell = program.cell_config(self.fl, config)

    def call(self, j):
        """Call ``j`` sweeps the instance seeds of set ``j mod pool``: cell
        c, seed s runs instance seed ``set[0] + c * seeds_per_cell + s``,
        so the results, cell-major, are in the set's order."""
        grid = self._run(self.world.dataset, self.world.shards, self.cell,
                         self._cfgs[j % len(self.sets)], **self.sweep)
        results = [r for row in grid for r in row]
        program.block(results)
        return results

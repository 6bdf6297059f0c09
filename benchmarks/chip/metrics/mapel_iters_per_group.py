"""mapel_iters_per_group: MAPEL polyblock iterations per group solved,
from the program's counters (``power.mapel_iters`` over
``power.mapel_groups``, ``repro.utils.spans``) as they stand after the
window: the warm-up call and the window's calls.  A program without the
counters reads nothing."""
from chipbench import hostspans


def read(ctx):
    try:
        from repro.utils import spans
    except ImportError:
        return None
    return hostspans.iters_per_group(spans.counts())

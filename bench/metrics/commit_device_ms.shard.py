"""Device time of the sharded commit's owner-scatter
(DoubleBuffer.commit -> state._sharded_scatter) per commit, in ms, on
the device that spends most time in it (the shards scatter buckets of
one size; the tail shard owns every row the run appends). One commit per
window of the traced stretch."""
from bench.lib import readers as R


def read(ctx):
    tr = ctx["trace"]
    w = ctx["counters"]["windows"]
    inside = R.in_module(R.SHARD_SCATTER_MODULE)
    per_device = [sum(e - s for s, e, name, mod in ops if inside(name, mod))
                  for ops in tr.ops.values()]
    if not w or not per_device or not max(per_device):
        return None
    return max(per_device) / 1e6 / w

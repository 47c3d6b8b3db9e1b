"""Device time of the commit scatter (DoubleBuffer.commit ->
state._scatter_rows) per commit, in the traced stretch; one commit per
window."""
from bench.lib import readers as R


def read(ctx):
    secs, n = ctx["trace"].op_seconds(R.in_module(R.SCATTER_MODULE))
    w = ctx["counters"]["windows"]
    if not n or not w:
        return None
    return 1e3 * secs / w

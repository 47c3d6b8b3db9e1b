"""Share of the ELO replay + selection roofline in the traced stretch.

Work (bench.lib.work.replay): the T = N*R records of each of Q live
queries replayed over M models, with the budget selection; bytes: the
records read once, ratings and choices written. Time: the device time
of the ELO kernel."""
from bench.lib import readers as R
from bench.lib import work
from bench.lib.peaks import roofline_seconds


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    secs, calls = tr.op_seconds(R.replay_kernel(ctx))
    if not calls or not secs:
        return None
    s = R.router_shapes(ctx)
    flops, nbytes = work.replay(c["window_rows"], s["n"] * s["r"], s["m"])
    _, t_min = roofline_seconds(flops, nbytes, ctx["peaks"])
    return R.percent(t_min * calls / secs)

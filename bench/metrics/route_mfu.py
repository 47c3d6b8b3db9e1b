"""The routing step's share of the peak of the chips it runs on: the
required FLOPs of every route dispatch the trace holds (retrieval over
every shard's rows and one replay, bench.lib.work.route_step, live rows
only) over the traced stretch's seconds times the peak FLOP/s of one
chip a shard. Dispatches are counted as runs of the similarity kernel,
per device."""
from bench.lib import readers as R
from bench.lib import work


def read(ctx):
    c, tr = ctx["counters"], ctx["trace"]
    _, calls = tr.op_seconds(R.similarity_kernel(ctx))
    if not calls or not tr.window_s:
        return None
    s = R.router_shapes(ctx)
    flops, _ = work.route_step(c["window_rows"], s["c"], s["d"], s["n"],
                               s["r"], s["m"])
    peak = s["shards"] * ctx["peaks"]["flops_per_s"]
    return R.percent(flops * calls / (tr.window_s * peak))

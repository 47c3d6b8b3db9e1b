"""Share of the retrieval roofline in the traced stretch.

Work (bench.lib.work.retrieval): 2*Q*C*D FLOPs; bytes: the f32 DB panel
read once, the queries, and the (Q, N) scores and rows. Q counts live
rows. On a v5e at route.paper1m.w256 the memory term bounds it (7.87 ms
against 4.19 ms of compute). Time: the device time of the similarity
kernel plus the top-k that reduces its panel."""
from bench.lib import readers as R
from bench.lib import work
from bench.lib.peaks import roofline_seconds


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    sim_s, calls = tr.op_seconds(R.similarity_kernel(ctx))
    top_s, _ = tr.op_seconds(R.panel_reducers(ctx))
    if not calls or not sim_s:
        return None
    s = R.router_shapes(ctx)
    # per device: each shard scans its own rows
    flops, nbytes = work.retrieval(c["window_rows"], s["c"] // s["shards"],
                                   s["d"], s["n"])
    _, t_min = roofline_seconds(flops, nbytes, ctx["peaks"])
    return R.percent(t_min * calls / (sim_s + top_s))

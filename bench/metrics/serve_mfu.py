"""The served steps' share of the chip's peak: required FLOPs of every
routing dispatch (bench.lib.work.route_step, live rows) and of every
request the checked full-width member answered (prefill of its prompt,
one decode per further token, attention over the prefix;
bench.lib.work.dense_request_flops), over the seconds spent inside the
window's serve calls times the peak FLOP/s. Below the knee the window's
wall time is set by the arrivals, so the steps' own time is the
denominator. The reduced stand-in members' work is not counted."""
from bench.lib import readers as R
from bench.lib import work


def read(ctx):
    c, cfg = ctx["counters"], ctx["cfg"]
    busy = c["serve_s"]
    if busy <= 0 or not c["flush_sizes"]:
        return None
    s = R.router_shapes(ctx)
    flops = sum(work.route_step(q, s["c"], s["d"], s["n"], s["r"], s["m"])[0]
                for q in c["flush_sizes"])
    ref = cfg["fleet"]["reference"]
    checked = cfg["fleet"]["checked"]
    plen = ctx["traffic"]["prompt_len"]
    for rid, resp in c["served"].items():
        if resp.model == checked:
            flops += work.dense_request_flops(ref, plen, int(c["max_new"][rid]))
    return R.percent(flops / (busy * ctx["peaks"]["flops_per_s"]))

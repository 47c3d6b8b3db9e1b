"""The served steps' share of the chip's peak in a cell whose checked
member is an expert-parallel share of a DeepSeek-V3 block model
(Moonlight): required FLOPs of every routing dispatch
(bench.lib.work.route_step, live rows) and of every request that member
answered (bench.lib.work_moe.request_flops: MLA over the prefix, the
dense layer, the shared experts, the head slice, and k * held / E
routed assignments per token, which is the expectation under an even
router, not the run's count), over the seconds spent inside the
window's serve calls times the peak FLOP/s. The reduced stand-in
members' work is not counted."""
from bench.lib import readers as R
from bench.lib import work, work_moe


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
            flops += work_moe.request_flops(ref, plen,
                                            int(c["max_new"][rid]))
    return R.percent(flops / (busy * ctx["peaks"]["flops_per_s"]))

"""Device time of the cross-shard merge per route dispatch, in ms: the
sharded route's all-gathers (all-gather, or all-gather-start and -done)
and the top-k over the (Q, S*N) pool of gathered candidates
(bench.lib.readers.merge_ops), mean over the devices. Dispatches are the
similarity kernel's runs. The bytes the merge gathers onto each device a
dispatch (bench.lib.work.merge_exchange) go to stderr beside it. A CPU
trace names its operations without HLO text, so a rehearsal reads
None."""
import sys

from bench.lib import readers as R
from bench.lib import work


def read(ctx):
    tr = ctx["trace"]
    secs, n = tr.op_seconds(R.merge_ops(ctx))
    _, calls = tr.op_seconds(R.similarity_kernel(ctx))
    if not n or not calls:
        return None
    s = R.router_shapes(ctx)
    nbytes = work.merge_exchange(ctx["counters"]["window_rows"], s["n"],
                                 s["r"], s["shards"])
    print(f"merge_ms.shard: {n / calls:.1f} merge ops and {nbytes:.0f} B "
          "gathered onto each device a dispatch", file=sys.stderr)
    return 1e3 * secs / calls

"""Device ms per decode step of the checked member's held experts: the
grouped gate, up and down matmuls, the dispatch and combine of the
window's sorted assignments, and the per-step cast of the held experts'
weights (bench.lib.readers_moe.expert_ops), over the member's
`serve.decode_step.<member>` markers in the trace. None where the
program leaves no such markers or ops."""
from bench.lib import readers_moe as RM


def read(ctx):
    return RM.per_decode_step(ctx, RM.expert_ops(ctx))

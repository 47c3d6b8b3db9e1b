"""Device ms per decode step of the checked member's absorbed
latent attention: its ops on the latent cache, the rope keys, the
scores and the latent queries and outputs
(bench.lib.readers_moe.mla_decode_ops), over the member's
`serve.decode_step.<member>` markers in the trace. None where the
program leaves no such markers or ops."""
from bench.lib import readers_moe as RM


def read(ctx):
    return RM.per_decode_step(ctx, RM.mla_decode_ops(ctx))

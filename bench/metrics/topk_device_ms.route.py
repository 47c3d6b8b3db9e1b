"""Device time of the top-k per route dispatch, in ms.

The `eagle.topk` scope (kernels/ref.py retrieve_replay_pipeline) holds
the live-row mask and lax.top_k, which XLA fuses into the one operation
that reads the similarity kernel's score panel. A TPU trace names each
operation by its HLO text, which carries the operands' names but not
the scope path (nor does any stat ProfileData exposes), so the top-k is
found as the route program's operations that take the named kernel
`eagle_similarity` as an operand; the live-row vector fused apart from
it (tens of ns) is left out. Dispatches are the kernel's own runs in the
traced stretch. A CPU trace names its operations without operands, so
a rehearsal reads None."""
from bench.lib import readers as R

KERNEL = "%eagle_similarity"


def _in_route(module):
    return module.startswith(R.ROUTE_MODULE)


def _kernel(name, module):
    return _in_route(module) and name.startswith(KERNEL)


def _reads_panel(name, module):
    head, _, operands = name.partition(" = ")
    return (_in_route(module) and not head.startswith(KERNEL)
            and KERNEL + "." in operands)


def read(ctx):
    tr = ctx["trace"]
    secs, n = tr.op_seconds(_reads_panel)
    _, calls = tr.op_seconds(_kernel)
    return 1e3 * secs / calls if n and calls else None

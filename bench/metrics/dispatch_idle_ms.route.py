"""Device idle time per window while the host is inside the route
dispatcher: idle time of the traced stretch whose innermost open host
span is one of RouteDispatcher's (`dispatch.put`, `dispatch.launch`,
`dispatch.readout`, `dispatch.route_result`; bench.lib.idle splits each
gap among the spans open across it), in ms, over the windows dispatched
in the trace (its `dispatch.launch` spans). Host spans and device ops
share the profiler's clock. None where the program leaves no dispatcher
spans in the trace."""
from bench.lib.idle import idle_by_span


def read(ctx):
    tr = ctx["trace"]
    windows = sum(1 for _, _, n in tr.spans if n == "dispatch.launch")
    if not windows or not tr.ops:
        return None
    idle = sum(s for n, s in idle_by_span(tr).items()
               if n.startswith("dispatch."))
    return 1e3 * idle / windows

"""Device idle time per decode step of the checked full-width member:
idle time of the traced stretch whose innermost open host span is that
member's `serve.decode_step.<member>` or `serve.readout.<member>`
marker (FleetModel.generate; bench.lib.idle splits each gap among the
spans open across it), in ms, over its decode steps in the trace. None
where the program leaves no such markers in the trace."""
from bench.lib.idle import idle_by_span


def read(ctx):
    tr = ctx["trace"]
    member = ctx["cfg"]["fleet"]["checked"]
    step, readout = "serve.decode_step." + member, "serve.readout." + member
    steps = sum(1 for _, _, n in tr.spans if n == step)
    if not steps or not tr.ops:
        return None
    idle = sum(s for n, s in idle_by_span(tr).items()
               if n in (step, readout))
    return 1e3 * idle / steps

"""Device idle time per window while the host is inside the commit:
idle time of the traced stretch whose innermost open host span is
DoubleBuffer.commit's `state.commit` or one of its parts
(`state.commit.gather`, `.upload`, `.scatter`; bench.lib.idle splits
each gap among the spans open across it), in ms, over the windows
dispatched in the trace (its `dispatch.launch` spans; one commit a
window). None where the program leaves no such spans in the trace."""
from bench.lib.idle import idle_by_span


def read(ctx):
    tr = ctx["trace"]
    windows = sum(1 for _, _, n in tr.spans if n == "dispatch.launch")
    if not windows or not tr.ops:
        return None
    idle = sum(s for n, s in idle_by_span(tr).items()
               if n.startswith("state.commit"))
    return 1e3 * idle / windows

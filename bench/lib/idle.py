"""Device idle time split by what the host was doing.

Trace.idle_gaps names each gap by the host span open at its middle, so a
gap that spans several of the program's spans goes whole to one of
them, whichever the middle falls in. The program-span readers need the
idle time inside a span however the gap around it lies: this splits
every gap at the boundaries of the spans open across it and gives each
piece to the innermost span open over it (the one that started last;
"none" where no span was open). The pieces of a gap add up to the gap,
and the gaps are those of Trace.idle_gaps (the first device, at least
`min_ns` long)."""
from __future__ import annotations

from typing import Dict


def idle_by_span(trace, min_ns: int = 1000) -> Dict[str, float]:
    """Idle seconds of the first device, by innermost open host span."""
    if not trace.ops:
        return {}
    busy = []
    for s, e, *_ in sorted(trace.ops[min(trace.ops)]):
        if busy and s <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], e)
        else:
            busy.append([s, e])
    spans = sorted(trace.spans, key=lambda x: x[0])
    out: Dict[str, float] = {}
    active, i = [], 0
    for (_, g0), (g1, _) in zip(busy, busy[1:]):
        if g1 - g0 < min_ns:
            continue
        while i < len(spans) and spans[i][0] < g1:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > g0]
        cuts = sorted({g0, g1} | {t for s, e, _ in active for t in (s, e)
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            inside = [sp for sp in active if sp[0] <= a and sp[1] >= b]
            name = max(inside, key=lambda sp: (sp[0], -sp[1]))[2] \
                if inside else "none"
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out

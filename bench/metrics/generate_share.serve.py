"""Share of the window's wall time (its start to the last answer)
inside the engine's serve.generate.<model> spans."""
from bench.lib import readers as R


def read(ctx):
    c = ctx["counters"]
    gen, n = R.engine_spans(ctx, "serve.generate.")
    wall = (c["t_last_ns"] - c["t0_ns"]) / 1e9
    return 100.0 * gen / wall if n and wall > 0 else None

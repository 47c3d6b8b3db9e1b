"""Host time of the online update per serve step: the engine's
serve.feedback and serve.commit spans over the window, divided by the
number of serve.step spans."""
from bench.lib import readers as R


def read(ctx):
    fb, _ = R.engine_spans(ctx, "serve.feedback")
    cm, _ = R.engine_spans(ctx, "serve.commit")
    _, steps = R.engine_spans(ctx, "serve.step")
    return 1e3 * (fb + cm) / steps if steps else None

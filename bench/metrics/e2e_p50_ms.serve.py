"""Median end-to-end latency of the served cell: each request due in the
window, from its due time to its last token on the host (a request never
answered in full counts until the run gave up on it), exact over every
request. Read from a traced run, so it carries the profiler's cost."""
import numpy as np


def read(ctx):
    e2e = ctx["counters"].get("e2e_ms")
    return float(np.percentile(e2e, 50)) if e2e is not None and len(e2e) \
        else None

"""95th percentile of the admission queue wait (arrival to flush,
AdmissionQueue's Completed.wait_us) over the window's requests, exact."""
import numpy as np


def read(ctx):
    w = ctx["counters"]["wait_us"]
    return float(np.percentile(w, 95)) / 1e3 if len(w) else None

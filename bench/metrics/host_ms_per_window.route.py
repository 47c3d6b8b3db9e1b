"""Host time between one window's choices reaching the host and the
next dispatch (feedback fold and commit enqueue), from the benchmark's
own host clock, mean per window of the traced stretch."""


def read(ctx):
    ms = ctx["counters"]["host_ms"]
    return float(ms.mean()) if len(ms) else None

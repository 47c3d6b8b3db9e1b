"""Padded rows over all rows dispatched: the route dispatcher's bucket
padding (dispatch_padded_rows_total against dispatch_rows_total) and
the generate groups' padding to the window bucket, over the window."""


def read(ctx):
    c = ctx["counters"]
    d = c["dispatch"]
    padded = d.get("padded_rows", 0) + c["gen_padded"]
    rows = d.get("rows", 0) + c["gen_rows"]
    return 100.0 * (padded - rows) / padded if padded else None

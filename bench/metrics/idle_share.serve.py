"""Share of the traced stretch in which no operation ran on the device."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.window_s or not tr.ops:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)

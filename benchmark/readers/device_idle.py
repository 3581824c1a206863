"""Share of the traced window in which no operation ran on the device:
1 minus the union of the device-op intervals over the window."""


def read(params, ctx):
    if not ctx.get("events") or not ctx.get("busy_s"):
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["trace_window_s"])

"""Per cent of the traced window in which no operation ran on the chip:
1 - (union of device operation intervals) / window, from the trace."""


def read(ctx):
    s = ctx["summary"]
    if s["window_s"] <= 0 or not s["devices"]:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])

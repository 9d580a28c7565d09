"""1 - the union of the device's operation intervals over the traced window,
on the busiest device."""


def read(ctx):
    t = ctx["trace"]
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_fullest_s"] / t["window_s"])

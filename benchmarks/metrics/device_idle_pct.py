"""Share of the traced span in which no operation ran on the device:
1 - union of the device's operation intervals / span, averaged over the
chips used. Source: the profiler's device trace."""


def read(ctx):
    t = ctx["trace"]
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

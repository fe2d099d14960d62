"""Of the idle seconds in the traced span's ten longest gaps, the
share during which the engine thread was in a host-work span, as
against waiting for requests or for the device (or under no span).
``device_idle_pct`` x this is the chip time host code costs. Source:
the device trace's gaps against the program's spans."""

from harness import spans


def read(ctx):
    log, gaps = spans.newest_log(), spans.gap_intervals(ctx)
    idle = sum(b - a for a, b in gaps)
    if log is None or not idle:
        return None
    segments = spans.self_segments(log.spans)
    return 100.0 * sum(spans.seconds_in(segments, a, b, spans.HOST_WORK)
                       for a, b in gaps) / idle

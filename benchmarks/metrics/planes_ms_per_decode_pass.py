"""The observability planes' part of ``host_ms_per_decode_pass``: self
time of ``engine.finalize`` (histograms, usage ledger, SLO, integrity
fold, request log, span export), ``engine.planes`` (goodput, cost
model, pass record) and ``engine.gauges`` inside the traced span, over
the decode passes collected in it. ROADMAP A3's price, as host time.
Source: the program's spans."""

from harness import spans


def read(ctx):
    return spans.ms_per_decode_pass(ctx, spans.PLANES)

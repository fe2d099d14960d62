"""Host work of the engine loop per decode pass: self time of every
host-work span of the engine thread (admit, the three dispatches, emit,
finalize, planes, prefill collect, gauges — not the waits) inside the
traced span, over the decode passes collected in it. Source: the
program's spans."""

from harness import spans


def read(ctx):
    return spans.ms_per_decode_pass(ctx, spans.HOST_WORK)

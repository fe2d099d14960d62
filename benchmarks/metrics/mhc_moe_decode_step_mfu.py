"""The ``xing4_0`` family's decode step program's share of peak while
it runs: operations the decode steps of the traced span need (active
parameters with compressed queries and the mHC projections, the stream
mixes, absorbed attention at the contexts the client saw) over the
summed device time of the decode program's executions x peak. Source:
device trace (program line); counts from
``harness/rooflines_mhc_moe.py``."""

from harness import rooflines_mhc_moe as need
from harness import window


def read(ctx):
    prog = ctx["trace"]["programs"].get("decode")
    contexts = window.decode_contexts(ctx)
    if not prog or not prog["device_s"] or not contexts or not ctx["peak"]:
        return None
    return 100.0 * need.decode_flops(ctx["cfg"], contexts) / (
        prog["device_s"] * ctx["peak"]["bf16_flops_per_s"])

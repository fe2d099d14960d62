"""The ``deepseek_v3`` family's decode step program's share of peak
while it runs: operations the decode steps of the traced span need
(active parameters, absorbed attention at the contexts the client saw)
over the summed device time of the decode program's executions x peak.
Source: device trace (program line)."""

from harness import rooflines_mla_moe as need
from harness import window


def read(ctx):
    prog = ctx["trace"]["programs"].get("decode")
    contexts = window.decode_contexts(ctx)
    if not prog or not prog["device_s"] or not contexts or not ctx["peak"]:
        return None
    return 100.0 * need.decode_flops(ctx["cfg"], contexts) / (
        prog["device_s"] * ctx["peak"]["bf16_flops_per_s"])

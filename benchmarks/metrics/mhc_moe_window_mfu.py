"""The whole served step's share of the chip's peak for the ``xing4_0``
family: every model operation of the tokens prefilled and decoded in
the traced span — active parameters only (attention with compressed
queries, the mHC projections and mixes, the shared expert, the routed
experts a token chose, the head once a sampled token) — over span x
chips x peak bf16 FLOP/s. Source: the device trace's span, the client's
tokens; operations from ``harness/rooflines_mhc_moe.py``."""

from harness import rooflines_mhc_moe as need
from harness import window


def read(ctx):
    t, peak = ctx["trace"], ctx["peak"]
    if not t["window_s"] or peak is None:
        return None
    flops = (need.decode_flops(ctx["cfg"], window.decode_contexts(ctx))
             + need.prefill_flops(ctx["cfg"],
                                  [b for _, b in window.prefill_spans(ctx)]))
    if not flops:
        return None
    return 100.0 * flops / (t["window_s"] * ctx["chips"]
                            * peak["bf16_flops_per_s"])

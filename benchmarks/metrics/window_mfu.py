"""The whole served step's share of the chip's peak: every model
operation of the tokens prefilled and decoded in the traced span, over
span x chips x peak bf16 FLOP/s. Bounds every kernel's roofline share
from above: work taken off a kernel's path still has to show here.
Source: the device trace's span, the client's tokens."""

from harness import window


def read(ctx):
    t, peak, rf = ctx["trace"], ctx["peak"], ctx["rooflines"]
    if not t["window_s"] or peak is None:
        return None
    flops = (rf.decode_flops(ctx["cfg"], window.decode_contexts(ctx))
             + rf.prefill_flops(ctx["cfg"],
                                [b for _, b in window.prefill_spans(ctx)]))
    if not flops:
        return None
    return 100.0 * flops / (t["window_s"] * ctx["chips"]
                            * peak["bf16_flops_per_s"])

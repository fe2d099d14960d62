"""The attention kernels' share of their roofline in prefill: the least
time for the causal attention that the prompts prefilled in the traced
span need — counted from the prompts, the same whichever kernel does it
(flash in bucket prefills, the paged kernel's chunk mask in walks) —
over the summed device time of attention-kernel events inside prefill
programs. Source: device trace (operation line)."""

from harness import window


def read(ctx):
    kernels = ctx["trace"]["kernels"]
    kernel_s = sum(by.get("prefill", 0.0) for by in kernels.values())
    spans = window.prefill_spans(ctx)
    if not kernel_s or not spans or not ctx["peak"]:
        return None
    rf = ctx["rooflines"]
    least, _bound = rf.least_time(*rf.prefill_attn_need(ctx["cfg"], spans),
                                  ctx["peak"])
    return 100.0 * least / kernel_s

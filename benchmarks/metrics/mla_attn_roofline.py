"""The latent (MLA) attention kernel's share of its roofline in decode:
the least time the chip could take for the cached rows read (context x
1,152 bytes a row, layer and step: one vector whatever the heads) and
the absorbed form's operations, for the tokens produced in the traced
span, over the summed device time of the kernel's events inside decode
programs. Source: device trace (operation line)."""

from harness import rooflines_mla_moe as need
from harness import window


def read(ctx):
    kernel_s = ctx["trace"]["kernels"].get("attention", {}).get("decode")
    contexts = window.decode_contexts(ctx)
    if not kernel_s or not contexts or not ctx["peak"]:
        return None
    least, _bound = ctx["rooflines"].least_time(
        *need.decode_attn_need(ctx["cfg"], contexts), ctx["peak"])
    return 100.0 * least / kernel_s

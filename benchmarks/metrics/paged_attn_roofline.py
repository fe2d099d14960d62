"""The paged attention kernel's share of its roofline in decode: the
least time the chip could take for the K/V bytes and the operations
that decode attention needs for the tokens produced in the traced span,
over the summed device time of the kernel's events inside decode
programs. Source: device trace (operation line)."""

from harness import window


def read(ctx):
    kernel_s = ctx["trace"]["kernels"].get("attention", {}).get("decode")
    contexts = window.decode_contexts(ctx)
    if not kernel_s or not contexts or not ctx["peak"]:
        return None
    rf = ctx["rooflines"]
    least, _bound = rf.least_time(*rf.decode_attn_need(ctx["cfg"], contexts),
                                  ctx["peak"])
    return 100.0 * least / kernel_s

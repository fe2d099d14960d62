"""The prefill step programs' share of peak while they run: operations
the prompts prefilled in the traced span need over the summed device
time of both kinds of prefill program (bucket and chunk) x peak.
Source: device trace (program line)."""

from harness import window


def read(ctx):
    prog = ctx["trace"]["programs"].get("prefill")
    spans = window.prefill_spans(ctx)
    if not prog or not prog["device_s"] or not spans or not ctx["peak"]:
        return None
    flops = ctx["rooflines"].prefill_flops(ctx["cfg"], [b for _, b in spans])
    return 100.0 * flops / (prog["device_s"]
                            * ctx["peak"]["bf16_flops_per_s"])

"""The decode step program's share of peak while it runs: operations
the decode steps of the traced span need (from the shapes and the
contexts the client saw) over the summed device time of the decode
program's executions x peak. Source: device trace (program line)."""

from harness import window


def read(ctx):
    prog = ctx["trace"]["programs"].get("decode")
    contexts = window.decode_contexts(ctx)
    if not prog or not prog["device_s"] or not contexts or not ctx["peak"]:
        return None
    flops = ctx["rooflines"].decode_flops(ctx["cfg"], contexts)
    return 100.0 * flops / (prog["device_s"]
                            * ctx["peak"]["bf16_flops_per_s"])

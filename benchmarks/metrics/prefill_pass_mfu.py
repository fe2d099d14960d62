"""The prefill step programs' share of peak while they run, counted by
the program: operations of the prefill passes enqueued inside the
traced span as the engine recorded them — a bucket pass's rows at
their real lengths, a chunk pass's rows from ``offset`` to ``offset +
length`` with their history — over the summed device time of both
kinds of prefill program x peak. The twin of ``prefill_step_mfu``,
which counts a prompt whole if its first token falls in the span; here
the edge error is one pass. Source: device trace (program line) and the
program's pass records."""

from harness import spans


def read(ctx):
    log, span = spans.newest_log(), spans.traced_span(ctx)
    prog = ctx["trace"]["programs"].get("prefill")
    if (log is None or span is None or not prog or not prog["device_s"]
            or not ctx["peak"]):
        return None
    flops, cfg = ctx["rooflines"].prefill_flops, ctx["cfg"]
    total = 0.0
    for p in log.passes:
        if not span[0] <= p["t0"] < span[1]:
            continue
        if p["kind"] == "prefill":
            total += flops(cfg, p["lens"])
        elif p["kind"] == "prefill_chunk":
            ends = [a + n for a, n in zip(p["offsets"], p["lens"])]
            # a walk's first chunk is a whole prompt of that length (the
            # head counted once); a later one adds what it adds
            total += flops(cfg, ends) - flops(
                cfg, [a for a in p["offsets"] if a])
    if not total:
        return None
    return 100.0 * total / (prog["device_s"]
                            * ctx["peak"]["bf16_flops_per_s"])

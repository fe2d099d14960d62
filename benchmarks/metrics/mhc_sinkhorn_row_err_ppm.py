"""How far from doubly stochastic the stream mixes of the traced span
were: the largest |row sum - 1| of any H_res of any decode step (every
sublayer, every row of the compiled batch), in parts per million. The
program takes it on the device after the last Sinkhorn round — columns
sum to one there by construction, the rows carry what the rounds left —
and writes it into the decode pass record (``mhc_row_err``). A
shortened Sinkhorn loop would shorten the decode step and raise this.
Source: the program's pass records, decode passes whose tokens reached
the host inside the traced span; a program without the counter (no
streams) gives nothing."""

from harness import spans


def read(ctx):
    log, span = spans.newest_log(), spans.traced_span(ctx)
    if log is None or span is None:
        return None
    errs = [p["mhc_row_err"] for p in log.passes
            if p["kind"] == "decode" and "mhc_row_err" in p
            and p.get("t1") is not None and span[0] <= p["t1"] < span[1]]
    return 1e6 * max(errs) if errs else None

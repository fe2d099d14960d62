"""Of the routed experts a decode layer-step could read, the share that
received a token: the program counts, on the device, how many experts
of each expert layer were handed a row in each decode step (the batch's
padding rows among them: they are multiplied too) and writes the sum
into the pass record. What sets the expert bytes of a step. Source: the
program's pass records (``experts_touched``), decode passes whose
tokens reached the host inside the traced span."""

from harness import spans


def read(ctx):
    log, span = spans.newest_log(), spans.traced_span(ctx)
    if log is None or span is None:
        return None
    cfg = ctx["cfg"]
    per_step = (cfg.get("n_routed_experts", 0)
                * (cfg.get("num_hidden_layers", 0)
                   - cfg.get("first_k_dense_replace", 0)))
    touched = could = 0
    for p in log.passes:
        if (p["kind"] == "decode" and "experts_touched" in p
                and p.get("t1") is not None and span[0] <= p["t1"] < span[1]):
            touched += p["experts_touched"]
            could += p["steps"] * per_step
    return 100.0 * touched / could if could else None

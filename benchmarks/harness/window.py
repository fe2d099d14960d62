"""What the client saw inside the traced part of the window, as the
roofline and MFU readers need it: which tokens were decoded there, at
what context, and which prompts were prefilled there.

The program keeps no per-pass record of rows and context lengths yet,
so the work is reconstructed from the client's side: a token belongs to
the traced span if it was received inside it, and a prompt if its first
token was. A prompt's chunk walk that straddles an edge of the span is
counted whole or not at all; over a span of some seconds the two edges
cancel on average, and the tracing issue's per-pass record will replace
this.
"""

from __future__ import annotations


def span(ctx) -> tuple[float, float]:
    return ctx["traced"]["t_start"], ctx["traced"]["t_end"]


def decode_contexts(ctx) -> list[int]:
    """Rows attended (the new token's own among them) by every decode
    step whose token arrived in the traced span. The first token of a
    request comes out of its prefill and is not a decode step."""
    lo, hi = span(ctx)
    out = []
    for r in ctx["records"]:
        base = len(r["prompt"])
        out.extend(base + i for i, t in enumerate(r["token_times"])
                   if i > 0 and lo <= t < hi)
    return out


def prefill_spans(ctx) -> list[tuple[int, int]]:
    """(0, prompt length) of every request whose first token arrived in
    the traced span."""
    lo, hi = span(ctx)
    return [(0, len(r["prompt"])) for r in ctx["records"]
            if r["token_times"] and lo <= r["token_times"][0] < hi]

"""From a slot to the first token: median over the window's answered
requests of ``first_token_at - admitted_at`` in the engine's flight
log — the prefill and every wait between its passes (behind the decode
pass in flight, between chunks). With ``queue_wait_p50_ms`` before it
and ``http_overhead_p50_ms`` around both it splits a request's time to
first token into three parts that add up. The host cannot time an
asynchronous chunk pass, so stall and prefill are not split further.
Source: the program's request log."""

from harness import spans


def read(ctx):
    waits = [(entry["first_token_at"] - entry["admitted_at"]) * 1e3
             for _, entry in spans.joined(ctx)
             if entry["admitted_at"] is not None]
    return ctx["stats"].percentile(waits, 50)

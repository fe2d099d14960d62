"""Median wait in the admission queue: ``admitted_at - submitted_at`` of
the engine's own request objects for the window's requests, collected
by the harness around ``Engine.submit``. Source: the program's
timestamps (host clock)."""


def read(ctx):
    waits = [(r["admitted_at"] - r["submitted_at"]) * 1e3
             for r in ctx["requests"] if r["admitted_at"] is not None]
    return ctx["stats"].percentile(waits, 50)

"""What the HTTP surface adds to a request's time to first token:
median over the window's answered requests of (client's first token -
client's send) - (engine's ``first_token_at`` - ``submitted_at``), the
client's record joined to the engine's flight-log entry by the digest
of the prompt. Each difference is taken within one clock. Source: the
program's request log against the generator's record."""

from harness import spans


def read(ctx):
    extra = [(rec["token_times"][0] - rec["sent"]
              - (entry["first_token_at"] - entry["submitted_at"])) * 1e3
             for rec, entry in spans.joined(ctx)]
    return ctx["stats"].percentile(extra, 50)

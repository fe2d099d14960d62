"""How late the load generator sent its requests (sent - due), 95th
percentile over the window: a starved generator must not be read as a
fast server. Source: the generator's own clock."""


def read(ctx):
    return ctx["stats"].percentile(ctx["stats"].late_ms(ctx["records"]), 95)

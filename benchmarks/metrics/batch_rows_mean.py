"""Mean active decode slots per decode pass over the window: the delta
of the engine's ``app_engine_batch_occupancy`` histogram (sum over
count). Source: the program's counter."""


def read(ctx):
    s0, n0 = ctx["before"]["occupancy"]
    s1, n1 = ctx["after"]["occupancy"]
    return (s1 - s0) / (n1 - n0) if n1 > n0 else None

"""Peak pages of the KV pool in use over pages reserved: the engine's
``kv_pages`` high-water mark (set-up allocates none) over its pool
size. Source: the program's counter."""


def read(ctx):
    pool = ctx["pool"]
    if not pool["pages"] or pool["peak_pages"] is None:
        return None
    return 100.0 * pool["peak_pages"] / pool["pages"]

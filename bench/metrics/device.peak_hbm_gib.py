"""device: peak device memory in use over the run, GiB
(``memory_stats()["peak_bytes_in_use"]``)."""


def read(ctx):
    return ctx.peak_bytes / 2 ** 30 if ctx.peak_bytes else None

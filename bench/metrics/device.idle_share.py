"""device: share of the traced window with no operation running, %:
1 - (union of the device's operation intervals) / (traced window)."""


def read(ctx):
    import trace_reduce
    span = ctx.window.trace_span
    if ctx.trace is None or span is None or span[1] is None:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(ctx.trace)
                    / (span[1] - span[0]))

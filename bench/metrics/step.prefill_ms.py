"""jitted steps: device time of one prefill-chunk program run, mean in
ms, from the profiler's "XLA Modules" events of the traced window."""
NAMES = ("_prefill",)


def read(ctx):
    import trace_reduce
    if ctx.trace is None:
        return None
    ev = trace_reduce.matching(ctx.trace.modules, NAMES)
    return 1e3 * trace_reduce.device_time(ev) / len(ev) if ev else None

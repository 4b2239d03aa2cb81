"""whole step: the least time of the traced steps at the chip's peaks
over the device time of their programs, %.

The least time counts what the algorithm needs (``costs.step_least_s``):
binarized GEMM operations at the int8 peak, every float operation at
the bf16 peak.  Program time is the "XLA Modules" events of the prefill
and decode programs in the traced window."""
import costs

NAMES = ("_prefill", "_decode")


def read(ctx):
    import trace_reduce
    if ctx.trace is None or not ctx.peaks:
        return None
    busy = trace_reduce.device_time(
        trace_reduce.matching(ctx.trace.modules, NAMES))
    c = ctx.cell.config
    least = 0.0
    for st in ctx.window.traced_steps:
        for row in st["prefill"]:
            least += costs.step_least_s(c, [row], ctx.peaks)
        if st["decode"]:
            least += costs.step_least_s(c, st["decode"], ctx.peaks)
    return 100.0 * least / busy if busy and least else None

"""kernels: paged attention's share of its roofline, %.

Least time: per program run, the QK and PV operations over each row's
true context at the bf16 peak, or the K and V of that context in the
pool's float32 plus queries and outputs at HBM bandwidth, whichever is
larger (``costs.attention``).  Kernel time: the device time of the
``paged_attention`` kernel's calls, found by the kernel's name
(``engine_spans.kernel_calls``)."""
import costs
import engine_spans

NAMES = ("paged_attention",)


def read(ctx):
    import trace_reduce
    if ctx.trace is None or not ctx.peaks:
        return None
    t = trace_reduce.device_time(engine_spans.kernel_calls(ctx.trace.ops,
                                                           NAMES))
    c, p = ctx.cell.config, ctx.peaks
    least = 0.0
    for st in ctx.window.traced_steps:
        calls = [[row] for row in st["prefill"]]
        if st["decode"]:
            calls.append(st["decode"])
        for rows in calls:
            least += costs.roofline_s(costs.attention(c, rows),
                                      p["bf16_flops_per_s"],
                                      p["hbm_bytes_per_s"])
    return 100.0 * least / t if t and least else None

"""kernels: paged attention's share of its roofline, %.

Least time: per program run, the QK and PV operations over each row's
true context at the bf16 peak, or the K and V of that context in the
pool's float32 plus queries and outputs at HBM bandwidth, whichever is
larger (``costs.attention``).  Kernel time: the paged-attention kernel's
device time.

The kernels carry no name of their own in the trace: a Pallas kernel is
a ``tpu_custom_call`` op named after the jitted step that holds it.  The
paged-attention kernel is the one that takes the cell's KV pools, whose
shape (blocks, block size, KV heads, head size) its label spells."""
import costs


def is_kernel(label: str, ctx) -> bool:
    e, c = ctx.cell.spec["engine"], ctx.cell.config
    pool = (f"f32[{e['num_blocks']},{e['block_size']},{c['n_kv_heads']},"
            f"{c['head_dim']}]")
    return "tpu_custom_call" in label and pool in label


def read(ctx):
    import trace_reduce
    if ctx.trace is None or not ctx.peaks:
        return None
    t = trace_reduce.device_time(
        [ev for ev in ctx.trace.ops if is_kernel(ev.label, ctx)])
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

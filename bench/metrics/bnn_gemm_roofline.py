"""kernels: the binarized GEMMs' share of their roofline, %.

Least time: per program run, every projection's 2*M*K*N operations at
the int8 peak or its bytes (packed weight, alpha, float activations in,
float32 out) at HBM bandwidth, whichever is larger (``costs.gemms``),
with M the real rows fed.  Kernel time: the device time of the fused
binarize-pack-XNOR-popcount kernel and of the weight-pack kernel: every
``tpu_custom_call`` op that is not paged attention (the 1-bit model has
no other Pallas kernel; see ``paged_attn_roofline.py``)."""
import costs


def read(ctx):
    import run
    import trace_reduce
    if ctx.trace is None or not ctx.peaks:
        return None
    attn = run.load_reader("paged_attn_roofline")
    t = trace_reduce.device_time(
        [ev for ev in ctx.trace.ops if "tpu_custom_call" in ev.label
         and not attn.is_kernel(ev.label, ctx)])
    c, p = ctx.cell.config, ctx.peaks
    least = 0.0
    for st in ctx.window.traced_steps:
        calls = [q for q, _ in st["prefill"]]
        if st["decode"]:
            calls.append(len(st["decode"]))
        for tokens in calls:
            least += costs.roofline_s(costs.gemms(c, tokens),
                                      p["int8_ops_per_s"],
                                      p["hbm_bytes_per_s"])
    return 100.0 * least / t if t and least else None

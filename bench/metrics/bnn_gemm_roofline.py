"""kernels: the binarized GEMMs' share of their roofline, %.

Least time: per program run, every projection's 2*M*K*N operations at
the int8 peak or its bytes (packed weight, alpha, float activations in,
float32 out) at HBM bandwidth, whichever is larger (``costs.gemms``),
with M the real rows fed.  Kernel time: the device time of the
XNOR-popcount GEMM kernel (``bnn_xnor_gemm``) and of the binarize-pack
kernel (``bnn_binarize_pack``), found by the kernels' names
(``engine_spans.kernel_calls``)."""
import costs
import engine_spans

NAMES = ("bnn_xnor_gemm", "bnn_binarize_pack")


def read(ctx):
    import trace_reduce
    if ctx.trace is None or not ctx.peaks:
        return None
    t = trace_reduce.device_time(engine_spans.kernel_calls(ctx.trace.ops,
                                                           NAMES))
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

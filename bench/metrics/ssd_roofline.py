"""kernels: the SSD's share of its roofline, %.

Least time: per program run, the recurrence's operations over the rows'
new tokens at the bf16 peak, or each row's slot state and conv tail read
and written once plus the tokens' inputs and outputs at HBM bandwidth,
whichever is larger (``costs.mixer``, the ssm family's).  Device time:
the ops of the step programs in the ``ssd`` scope (the conv, the scan or
dual form and the gated norm) and the state traffic that
``step.ssm_state_ms`` reads (the ``ssm_state`` scope's slot gathers and
scatters, and unscoped copies of a whole slot pool).  The state's bytes
are part of the least time, and XLA fuses the slot gathers and scatters
with the recurrence's reads and writes of the state, so the ``ssd``
scope alone leaves out part of the work.  A program without the scopes
reads nothing."""
import costs
import engine_spans

SCOPE = "ssd"
STATE_SCOPE = "ssm_state"


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    ms = engine_spans.scope_ms_per_step(ctx, SCOPE)
    got = engine_spans._loaded(ctx) or {}
    if not ms or not any(engine_spans.in_scope(p, SCOPE)
                         for p in got.get("paths") or []):
        return None
    ms += engine_spans.scope_ms_per_step(ctx, STATE_SCOPE,
                                         with_pool_copies=True) or 0.0
    t = ms * got["steps"] / 1e3
    c, p = ctx.cell.config, ctx.peaks
    least = 0.0
    for st in ctx.window.traced_steps:
        calls = [[row] for row in st["prefill"]]
        if st["decode"]:
            calls.append(st["decode"])
        for rows in calls:
            least += costs.roofline_s(costs.mixer(c, rows),
                                      p["bf16_flops_per_s"],
                                      p["hbm_bytes_per_s"])
    return 100.0 * least / t if least else None

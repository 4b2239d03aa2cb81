"""state pools: device time of the recurrent slot state's traffic, ms
per step.

The ops in the ``ssm_state`` scope (each layer's gather of the rows'
slots from its pool and scatter of the new state back, the speculative
snapshots, and the separately jitted slot zeroing, restores and
prefix-snapshot copies), plus every XLA-inserted copy of a whole state
pool that the built engine holds (``ctx.pools``) and that carries no
scope, over the window's ``engine.step`` spans.  A program without the
scope reads nothing."""
import engine_spans

SCOPE = "ssm_state"


def read(ctx):
    v = engine_spans.scope_ms_per_step(ctx, SCOPE, with_pool_copies=True)
    paths = (engine_spans._loaded(ctx) or {}).get("paths") or []
    if v is None or not any(engine_spans.in_scope(p, SCOPE) for p in paths):
        return None
    return v

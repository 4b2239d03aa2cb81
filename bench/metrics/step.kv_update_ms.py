"""jitted steps: device time of the KV pool update, ms per step.

The ops of the step programs in the ``kv_update`` scope (the scatter of
new keys and values into the paged pools), plus every XLA-inserted copy
of a whole state pool that the built engine holds (``ctx.pools``) and
that carries no scope (``engine_spans.whole_pool_copy``), over the
window's ``engine.step`` spans."""
import engine_spans


def read(ctx):
    return engine_spans.scope_ms_per_step(ctx, "kv_update",
                                          with_pool_copies=True)

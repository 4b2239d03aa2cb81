"""state pools: device idle time while the engine prepares a call, ms per
step.

Of the device's idle time in the traced window, the part in which the
innermost engine phase span on the host was a ``prepare`` span
(``engine.prefill.prepare``, ``engine.decode.prepare``,
``engine.spec.prepare``): growing and copy-on-write of the block pools,
swap-ins nested there, the host rebuild of block tables, slots and
sampling rows, and their upload; over the window's ``engine.step``
spans (``engine_spans.idle_by_phase``)."""
import engine_spans


def read(ctx):
    return engine_spans.idle_ms_per_step(ctx, "prepare")

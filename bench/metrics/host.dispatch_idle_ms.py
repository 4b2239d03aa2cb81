"""engine loop: device idle time while the engine dispatches a step
program, ms per step.

Of the device's idle time in the traced window, the part in which the
innermost engine phase span on the host was a ``dispatch`` span (the
jitted call up to its return), over the window's ``engine.step`` spans
(``engine_spans.idle_by_phase``)."""
import engine_spans


def read(ctx):
    return engine_spans.idle_ms_per_step(ctx, "dispatch")

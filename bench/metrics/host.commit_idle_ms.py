"""engine loop: device idle time while the engine commits sampled tokens,
ms per step.

Of the device's idle time in the traced window, the part in which the
innermost engine phase span on the host was a ``commit`` span (token
append, finishing requests, prefix registration, commit callbacks),
over the window's ``engine.step`` spans (``engine_spans.idle_by_phase``)."""
import engine_spans


def read(ctx):
    return engine_spans.idle_ms_per_step(ctx, "commit")

"""scheduler: device idle time while the engine schedules, ms per step.

Of the device's idle time in the traced window (the gaps between its
operations, as ``device.idle_share`` counts them), the part in which the
innermost engine phase span on the host was ``engine.schedule``
(``Scheduler.schedule``), over the ``engine.step`` spans of the window
(``engine_spans.idle_by_phase``)."""
import engine_spans


def read(ctx):
    return engine_spans.idle_ms_per_step(ctx, "schedule")

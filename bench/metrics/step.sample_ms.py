"""jitted steps: device time of token sampling, ms per step.

The ops of the step programs in the ``sample`` scope (temperature,
top-k/top-p and the draw over the whole vocabulary), over the window's
``engine.step`` spans."""
import engine_spans


def read(ctx):
    return engine_spans.scope_ms_per_step(ctx, "sample")

"""kernels: device time of the in-step weight pack, ms per step.

The ops of the step programs in the ``weight_pack`` scope (binarizing
and bit-packing each projection's float weight, and its column scales,
inside every step), over the window's ``engine.step`` spans."""
import engine_spans


def read(ctx):
    return engine_spans.scope_ms_per_step(ctx, "weight_pack")

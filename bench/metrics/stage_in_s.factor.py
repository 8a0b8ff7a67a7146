"""Host seconds per factor before the device copy: the
``repro.factor.tile`` and ``repro.factor.cast`` spans inside each
``repro.factor`` span of the window (tiling the f64 matrix, casting it
to the compute dtype), averaged over the factors."""
from bench import spans


def read(ctx):
    return spans.phase_s(ctx, ("tile", "cast"))

"""Device-busy seconds per factor of the ops made inside the executor's
``round.<class>`` scopes (a tile rounded through a narrower class),
inside each ``repro.factor`` span of the window."""
from bench import spans


def read(ctx):
    return spans.scope_busy_s(ctx, "round")

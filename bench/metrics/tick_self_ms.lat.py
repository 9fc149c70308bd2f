"""tick_self_ms.lat: host time of each ``inflight.tick`` span (one
``step()``) outside the phase spans inside it, averaged over the ticks,
in ms."""
from program_spans import tick_self_ms


def read(ctx):
    return tick_self_ms(ctx.trace)

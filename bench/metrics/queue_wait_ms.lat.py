"""queue_wait_ms.lat: wall-clock time requests waited in the scheduler's
queue before admission, the ``wait_ms`` of the ``inflight.admit`` spans
over the rows they admitted, in ms."""
from program_spans import per_row


def read(ctx):
    return per_row(ctx.trace, "inflight.admit", "wait_ms")

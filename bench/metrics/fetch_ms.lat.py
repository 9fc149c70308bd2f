"""fetch_ms.lat: ``fetch_ms`` in a latency cell, in ms: host time of the
``inflight.fetch`` spans over the requests they carried."""
from program_spans import per_row


def read(ctx):
    return per_row(ctx.trace, "inflight.fetch")

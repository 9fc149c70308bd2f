"""fetch_mb_per_req: bytes the ``inflight.fetch`` spans copied to the
host over the requests they carried, in MB (10^6 B). A group padded to
a power of two moves its pad rows too."""
from program_spans import per_row


def read(ctx):
    b = per_row(ctx.trace, "inflight.fetch", "bytes")
    return None if b is None else b * 1e-6

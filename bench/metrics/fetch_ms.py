"""fetch_ms: host time of the program's ``inflight.fetch`` spans (each
retiring group's readout copied to the host, ``finalize_retired``) over
the requests they carried, in ms."""
from program_spans import per_row


def read(ctx):
    return per_row(ctx.trace, "inflight.fetch")

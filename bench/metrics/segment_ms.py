"""segment_ms: device time of one run of the segment program
(``Integrator.segment_cell``, jitted as ``run``), in ms."""
from metric_kit import SEGMENT, mean_run_ms


def read(ctx):
    return mean_run_ms(ctx, SEGMENT)

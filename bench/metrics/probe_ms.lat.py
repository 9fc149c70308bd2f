"""probe_ms.lat: device time of one run of the admission probe
(``_SlotPool._cells``' ``probe``), in ms."""
from metric_kit import PROBE, mean_run_ms


def read(ctx):
    return mean_run_ms(ctx, PROBE)

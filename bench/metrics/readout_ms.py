"""readout_ms: per request completed in the traced window, the device
time of the readout program plus the host-visible time of the
device-to-host copies (dispatch to arrival, then the host's re-layout),
in ms. The copies are those of every transfer in the window; the logits
are all but all of their bytes."""
from metric_kit import readout_runs
from xplane import overlap


def read(ctx):
    tr = ctx.trace
    if not tr.devices or not ctx.completions:
        return None
    runs = readout_runs(ctx, tr.devices[0])
    if not runs:
        return None
    device = sum(e - s for s, e in runs)
    copies = overlap(tr.d2h(), tr.t0, tr.t1)
    return (device + copies) * 1e-6 / len(ctx.completions)

"""step_idle_ms.lat: device-idle time inside each ``bench.step`` span
(one scheduler tick), averaged over the ticks of the traced window, in
ms: the host work that the first chip waits on."""
from xplane import overlap


def read(ctx):
    tr = ctx.trace
    steps = [(s, e) for n, s, e in tr.spans if n == "bench.step"]
    if not tr.devices or not steps:
        return None
    busy = tr.devices[0].busy
    idle = sum((e - s) - overlap(busy, s, e) for s, e in steps)
    return idle * 1e-6 / len(steps)

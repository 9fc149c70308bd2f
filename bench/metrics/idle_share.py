"""idle_share: share of the traced window in which no operation ran on
a chip, averaged over the chips in use, in %."""


def read(ctx):
    tr = ctx.trace
    if not tr.devices or tr.window_ns <= 0:
        return None
    busy = [tr.busy_ns(d) for d in tr.devices[:ctx.chips]]
    return 100.0 * (1.0 - sum(busy) / len(busy) / tr.window_ns)

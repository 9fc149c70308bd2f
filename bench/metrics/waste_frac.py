"""waste_frac: slot-steps of the whole window that advanced no request
(masked or empty rows), from the scheduler's counters, in %."""


def read(ctx):
    a, b = ctx.counters["start"], ctx.counters["end"]
    total = b["slot_steps"] - a["slot_steps"]
    if total <= 0:
        return None
    return 100.0 * (1.0 - (b["useful_steps"] - a["useful_steps"]) / total)

"""Helpers shared by the per-layer metric readers (``metrics/<name>.py``).

Each reader is ``metrics/<metric>.py`` with ``read(ctx)``, returning the
metric's value, or None where the trace holds nothing for it. ``ctx``
carries the reduced trace (``xplane.Trace``), the scheduler's counters at
the window's ends, the completions in the traced span, the configuration as run
(``sizes``) and its layout module (``layout``), the prompt length, the
chip's peaks and the chips in use.
"""
from __future__ import annotations

import re

SEGMENT = "jit_run"          # Integrator.segment_cell's jitted ``run``
PROBE = "jit_probe"          # _SlotPool._cells' admission probe
KERNEL = 'custom_call_target="tpu_custom_call"'


def mean_run_ms(ctx, name: str):
    """Mean device time of one run of program ``name``, over the chips."""
    runs = [e - s for d in ctx.trace.devices
            for s, e in ctx.trace.module_runs(d, name)]
    return sum(runs) / len(runs) * 1e-6 if runs else None


def readout_runs(ctx, dev):
    """Runs of the readout program: the program whose operation writes
    float32 logits over the whole vocabulary."""
    logits = re.compile(r"^%\S+ = f32\[\d+,\d+," + str(ctx.sizes["vocab_size"])
                        + r"\]")
    runs = []
    for name, s, e in dev.modules:
        if not ctx.trace.t0 <= s < ctx.trace.t1:
            continue
        if any(logits.match(t) for t, a, _ in dev.ops if s <= a <= e):
            runs.append((s, e))
    return runs

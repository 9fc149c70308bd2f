"""hyper_step_roofline: the fused update kernel's least possible time
(``counts.kernel_floor_s`` from each call's operand shapes and memory
spaces) over its measured device time, summed over its calls in the
segment program, in %."""
import counts
from metric_kit import KERNEL, SEGMENT


def read(ctx):
    floor = spent = 0.0
    for dev in ctx.trace.devices:
        for text, s, e in dev.ops:
            if KERNEL not in text or not ctx.trace.t0 <= s < ctx.trace.t1:
                continue
            i = dev.module_of(s)
            if i is None or dev.modules[i][0] != SEGMENT:
                continue
            floor += counts.kernel_floor_s(text, ctx.peaks)[0]
            spent += (e - s) * 1e-9
    return 100.0 * floor / spent if spent else None

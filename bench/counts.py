"""Operations and bytes of the served work, and the chips' peaks.

The FLOP arithmetic is that of ``roofline/costmodel.py``, kept with the
benchmark so that the yardstick does not move with the program: the
vocabulary readout here, a group's and the tail's layers in the
configuration's layout (``layouts/<layout>.py``). The sizes are a
configuration file's keys (``spec.model_sizes``).
"""
from __future__ import annotations

import json
import os
import re
from typing import Dict, Tuple

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak rates of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; add them with their source")
    return table[device_kind]


def readout_flops(m: dict, S: int) -> float:
    return 2 * S * m["hidden_size"] * m["vocab_size"]


def request_flops(layout, m: dict, S: int, nfe: int) -> float:
    """Model FLOPs of one scored request: ``nfe`` field evaluations, the
    tail layers and the readout (the correction g and norms are left
    out)."""
    return (nfe * layout.field_eval_flops(m, S) + layout.tail_flops(m, S)
            + readout_flops(m, S))


# ---------------------------------------------------- kernel operands ----

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_ARRAY = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f16|f32|f64)"
                    r"\[([\d,]*)\](\{[^}]*\})?")


def _arrays(text: str):
    """(bytes, memory space, elements) of each array shape written in
    HLO text."""
    for dt, dims, layout in _ARRAY.findall(text):
        n = 1
        for x in filter(None, dims.split(",")):
            n *= int(x)
        space = re.search(r"S\((\d+)\)", layout or "")
        yield n * _DTYPE_BYTES[dt], int(space.group(1)) if space else 0, n


def custom_call_traffic(text: str) -> Tuple[Dict[int, int], Dict[int, int]]:
    """Bytes read and written by one kernel call, by memory space (0 is
    HBM, 1 the core's vector memory), from the call's HLO text:
    ``%name = <result shape> custom-call(<operands>), ...``."""
    head, _, rest = text.partition(" custom-call(")
    operands = rest.split("), ", 1)[0]
    read: Dict[int, int] = {}
    written: Dict[int, int] = {}
    for b, sp, _ in _arrays(operands):
        read[sp] = read.get(sp, 0) + b
    for b, sp, _ in _arrays(head.split(" = ", 1)[-1]):
        written[sp] = written.get(sp, 0) + b
    return read, written


def rk_update_flops(text: str) -> float:
    """Operations of one fused update ``z + eps*sum_j b_j r_j + eps^2 g``:
    a multiply and an add for each array operand after z, per element."""
    head, _, rest = text.partition(" custom-call(")
    sizes = [n for _, _, n in _arrays(rest.split("), ", 1)[0])]
    big = [n for n in sizes if n == max(sizes)]
    return 2.0 * big[0] * (len(big) - 1) if big else 0.0


def kernel_floor_s(text: str, peak: Dict[str, float]) -> Tuple[float, str]:
    """Least time the chip could take for one kernel call, and what bounds
    it: operations at peak, HBM bytes at HBM bandwidth, or vector-memory
    reads and writes at their bandwidths."""
    read, written = custom_call_traffic(text)
    bounds = {
        "compute": rk_update_flops(text) / peak["bf16_flops_per_s"],
        "hbm": (read.get(0, 0) + written.get(0, 0))
        / peak["hbm_bytes_per_s"],
        "vmem_read": read.get(1, 0) / peak["vmem_read_bytes_per_s"],
        "vmem_write": written.get(1, 0) / peak["vmem_write_bytes_per_s"],
    }
    which = max(bounds, key=bounds.get)
    return bounds[which], which

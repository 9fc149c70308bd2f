"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The JAX profiler writes one ``.xplane.pb`` per traced window. In it:

  * each chip is a plane ``/device:TPU:<n>``; its line ``XLA Modules``
    holds one event per program run (``jit_<name>(<fingerprint>)``) and
    its line ``XLA Ops`` one event per operation, named by the
    operation's HLO text (result and operand shapes included);
  * the host is the plane ``/host:CPU``: the harness's own spans
    (``bench.submit``, ``bench.step``, ``bench.collect``) and the runtime's
    copies (``D2H Dispatch``, ``tpu::System::TransferFromDevice`` with its
    ``=>IssueEvent=>Done``, then ``XlaDelinearize`` into host layout).

All times are nanoseconds on the trace's one clock. The window is the
stretch from the first to the last harness span.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]
SPANS = ("bench.submit", "bench.step", "bench.collect")
_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def overlap(merged: List[Interval], t0: float, t1: float) -> float:
    """Length of ``merged`` (sorted, disjoint) inside [t0, t1]."""
    i = max(bisect.bisect_right([s for s, _ in merged], t0) - 1, 0)
    total = 0.0
    for s, e in merged[i:]:
        if s >= t1:
            break
        total += max(0.0, min(e, t1) - max(s, t0))
    return total


def gaps(merged: List[Interval], t0: float, t1: float) -> List[Interval]:
    out, t = [], t0
    for s, e in merged:
        if e <= t0 or s >= t1:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < t1:
        out.append((t, t1))
    return out


def module_name(event_name: str) -> str:
    """``jit_run(1234)`` -> ``jit_run``."""
    return _MODULE.match(event_name).group(1)


class Device:
    """One chip's programs and operations."""

    def __init__(self, name: str):
        self.name = name
        self.modules: List[Tuple[str, float, float]] = []  # name, start, end
        self.ops: List[Tuple[str, float, float]] = []      # text, start, end
        self.busy: List[Interval] = []

    def module_of(self, t: float) -> Optional[int]:
        """Index of the module run that holds time ``t``."""
        i = bisect.bisect_right([s for _, s, _ in self.modules], t) - 1
        if i >= 0 and self.modules[i][1] <= t <= self.modules[i][2]:
            return i
        return None


class Trace:
    def __init__(self, path: str):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        self.devices: List[Device] = []
        self.spans: List[Tuple[str, float, float]] = []
        self.host: List[Tuple[str, str, float, float, dict]] = []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                self.devices.append(self._device(plane))
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        if e.name in SPANS:
                            self.spans.append((e.name, e.start_ns, e.end_ns))
                        else:
                            self.host.append((line.name, e.name, e.start_ns,
                                              e.end_ns, dict(e.stats)))
        self.devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
        self.spans.sort(key=lambda s: s[1])
        self.t0 = min((s for _, s, _ in self.spans), default=0.0)
        self.t1 = max((e for _, _, e in self.spans), default=0.0)

    @staticmethod
    def _device(plane) -> Device:
        d = Device(plane.name)
        for line in plane.lines:
            if line.name == "XLA Modules":
                d.modules = sorted((module_name(e.name), e.start_ns, e.end_ns)
                                   for e in line.events)
            elif line.name == "XLA Ops":
                d.ops = sorted(((e.name, e.start_ns, e.end_ns)
                                for e in line.events), key=lambda o: o[1])
        d.modules.sort(key=lambda m: m[1])
        d.busy = union([(s, e) for _, s, e in d.ops])
        return d

    @property
    def window_ns(self) -> float:
        return self.t1 - self.t0

    def busy_ns(self, dev: Device) -> float:
        return overlap(dev.busy, self.t0, self.t1)

    def module_runs(self, dev: Device, name: str) -> List[Tuple[float, float]]:
        return [(s, e) for m, s, e in dev.modules
                if m == name and self.t0 <= s < self.t1]

    def d2h(self) -> List[Interval]:
        """Host-visible device-to-host copies: from each dispatch to its
        completion, and the host's re-layout of what arrived."""
        dispatch = sorted(s for _, n, s, _, _ in self.host
                          if n == "D2H Dispatch")
        issued = {st.get("_c"): s for _, n, s, _, st in self.host
                  if n == "tpu::System::TransferFromDevice=>IssueEvent"}
        out = []
        for _, n, s, e, st in self.host:
            if n == "tpu::System::TransferFromDevice=>IssueEvent=>Done":
                t = issued.get(st.get("_c"), s)
                i = bisect.bisect_right(dispatch, t) - 1
                out.append((dispatch[i] if i >= 0 else t, e))
            elif n == "XlaDelinearize":
                out.append((s, e))
        return union(out)

    def d2h_bytes(self) -> int:
        return int(sum(st.get("size", 0) for _, n, s, _, st in self.host
                       if n == "tpu::System::TransferFromDevice"
                       and self.t0 <= s < self.t1))


def load(trace_dir: str) -> Optional[Trace]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return Trace(max(files, key=os.path.getmtime)) if files else None


def _short_op(text: str) -> str:
    """``%fusion.12 = bf16[...] ...`` -> ``fusion``."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def breakdown(tr: Trace, top: int = 10) -> Dict[str, list]:
    """Device operations that took most time (by program and operation,
    over every chip), and the longest idle gaps of the first chip by the
    harness span and host activity they fell in."""
    ops: Dict[str, float] = {}
    for dev in tr.devices:
        for text, s, e in dev.ops:
            if not tr.t0 <= s < tr.t1:
                continue
            i = dev.module_of(s)
            mod = dev.modules[i][0] if i is not None else "?"
            key = f"{mod}/{_short_op(text)}"
            ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
    device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    idle: List[Tuple[str, float]] = []
    if tr.devices:
        copies = tr.d2h()
        for s, e in gaps(tr.devices[0].busy, tr.t0, tr.t1):
            mid = (s + e) / 2
            where = next((n for n, a, b in tr.spans if a <= mid <= b),
                         "between spans")
            if overlap(copies, s, e) > 0.5 * (e - s):
                where += "/device-to-host copy"
            idle.append((where, (e - s) * 1e-9))
        idle.sort(key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": [[k, v] for k, v in idle[:top]]}

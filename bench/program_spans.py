"""Spans the program writes into the profiler's trace.

``launch/scheduler.py`` marks the phases of its served path with
``jax.profiler`` annotations on the host, on the same clock as the
device planes: ``inflight.tick`` (one ``step()``), and inside it
``inflight.admit`` (``rows``, ``wait_ms``), ``inflight.launch``,
``inflight.meta_wait``, ``inflight.readout`` (``rows``, ``width``) and
``inflight.fetch`` (``rows``, ``width``, ``bytes``). ``xplane.Trace``
keeps them in ``Trace.host`` with their arguments as stats. A program
without them gives no span, and every reader then returns None.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from xplane import overlap, union

PREFIX = "inflight."
TICK = "inflight.tick"


def spans(tr, name: str) -> List[Tuple[float, float, dict]]:
    """(start, end, args) of each span ``name`` that starts in the
    window [t0, t1)."""
    return [(s, e, st) for _, n, s, e, st in tr.host
            if n == name and tr.t0 <= s < tr.t1]


def per_row(tr, name: str, arg: Optional[str] = None) -> Optional[float]:
    """Over the spans ``name`` in the window: the sum of their ``arg``
    (of their lengths in ms, without ``arg``) over the sum of their
    ``rows``."""
    found = spans(tr, name)
    rows = sum(st.get("rows", 0) for _, _, st in found)
    if not rows:
        return None
    total = sum(st.get(arg, 0) if arg else (e - s) * 1e-6
                for s, e, st in found)
    return total / rows


def tick_self_ms(tr) -> Optional[float]:
    """Mean over the ticks in the window of the tick's length less the
    union of the phase spans inside it, in ms: the tick's own host work
    (queue bookkeeping, counters, Python) outside any named phase."""
    ticks = spans(tr, TICK)
    if not ticks:
        return None
    phases = union([(s, e) for _, n, s, e, _ in tr.host
                    if n.startswith(PREFIX) and n != TICK])
    return sum((e - s) - overlap(phases, s, e)
               for s, e, _ in ticks) / len(ticks) * 1e-6


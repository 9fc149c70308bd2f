"""Drive the served path on the wall clock.

The server is the program's own stack, built as users build it:
``lm_depth_model`` over the benchmark's weights, then
``InflightScheduler``. The window calls ``submit`` as requests come due
and ``step`` until it closes; every timestamp is ``time.perf_counter``
around those calls (never the scheduler's virtual clock).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import numpy as np

import traffic
import weights

WARM_BASE = 10 ** 9       # request indices of warm-up prompts
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class CompileCount:
    """Compiles and traces JAX reports, from the monitoring listener."""

    def __init__(self):
        self.n = 0

    def on(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.n += 1


_COUNTER: Optional[CompileCount] = None


def compile_counter() -> CompileCount:
    """The process's one counter (JAX's listeners cannot be removed)."""
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCount()
        jax.monitoring.register_event_duration_secs_listener(_COUNTER.on)
    return _COUNTER


def build(cell, cfg, seed: int):
    """Weights from the seed, the depth model and the scheduler."""
    from repro.launch.engine import EngineConfig, lm_depth_model
    from repro.launch.scheduler import InflightScheduler
    from repro.models.lm import init_lm

    params = weights.model_weights(cell.layout, cfg, seed)
    want = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise SystemExit("bench: the benchmark's weights do not fit the "
                         "program's parameter tree")
    srv = cell.server
    hyper = srv["solver"].startswith("hyper_")
    gw = weights.g_weights(cfg, seed, srv["g_rank"], srv["g_out_std"]) \
        if hyper else None
    model = lm_depth_model(params, cfg, solver=srv["solver"],
                           fused=srv["fused"], refinable=hyper,
                           g_params=gw, rank=srv.get("g_rank", 32))
    ecfg = EngineConfig(buckets=tuple(srv["buckets"]),
                        tol=srv.get("tol", 1e-2), solver=srv["solver"],
                        controller=srv["controller"],
                        fixed_K=srv.get("fixed_K", 0), fused=srv["fused"])
    mesh = None
    if srv.get("mesh"):
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(int(srv["mesh"]))
    sched = InflightScheduler(model, ecfg, slots=srv["slots"],
                              seg=srv["seg"], mesh=mesh)
    return params, gw, sched


def warm(sched, mix: dict, vocab: int, seed: int) -> None:
    """Compile every shape the window uses: a backlog refills the whole
    pool at once, an open loop admits and retires any count up to the
    pool width. The first admission fills an empty pool (its own path),
    so every refill count follows it."""
    slots = sched.slots
    counts = [slots] + ([slots] if mix["arrivals"] == "backlog"
                        else list(range(1, slots + 1)))
    for n in counts:
        for j in range(n):
            sched.submit(traffic.prompt(mix, vocab, seed, WARM_BASE + j))
        while sched.pending:
            sched.step()


@dataclasses.dataclass
class Window:
    """What the window and its drain left: per-request records, the
    sample kept for the check, the scheduler's counters at the window's
    ends, and the completions of the traced span."""

    seconds: float = 0.0              # measured length
    due: Dict[int, float] = dataclasses.field(default_factory=dict)
    done: Dict[int, float] = dataclasses.field(default_factory=dict)
    status: Dict[int, str] = dataclasses.field(default_factory=dict)
    in_window: List[int] = dataclasses.field(default_factory=list)
    samples: List[dict] = dataclasses.field(default_factory=list)
    late_s: List[float] = dataclasses.field(default_factory=list)
    queue: List[tuple] = dataclasses.field(default_factory=list)
    host_s: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {"submit": 0.0, "step": 0.0, "collect": 0.0})
    longest_s: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {"step": 0.0, "collect": 0.0})
    compiles: int = 0
    counters: Dict[str, dict] = dataclasses.field(default_factory=dict)
    traced: Optional[dict] = None


class Sampler:
    """Keeps a seeded sample of completions for the check: request ``i``
    is kept with probability 1/``every`` (from ``(seed, i)``), up to
    ``most``, with its served argmax at ``positions`` seeded positions and
    its full logit rows at the first ``rows`` of them. Reading a few
    positions keeps the sample's host work inside the window small."""

    def __init__(self, check: dict, seed: int):
        self.every, self.most = int(check["sample_every"]), \
            int(check["sample_most"])
        self.positions = int(check["positions_per_request"])
        self.rows, self.seed = int(check["rows_per_request"]), seed

    def _rng(self, i: int):
        return np.random.default_rng(
            [self.seed & 0xFFFFFFFF, self.seed >> 32, i, 0xC4EC])

    def wants(self, i: int, kept: int) -> bool:
        return kept < self.most and self._rng(i).random() * self.every < 1

    def keep(self, i: int, tokens: np.ndarray, c) -> dict:
        out = np.asarray(c.outputs)
        rng = self._rng(i)
        rng.random()
        pos = rng.choice(out.shape[0], min(self.positions, out.shape[0]),
                         replace=False).astype(np.int32)
        picked = out[pos]
        return {"i": i, "tokens": tokens, "K": int(c.K), "nfe": int(c.nfe),
                "pos": pos, "argmax": np.argmax(picked, -1).astype(np.int32),
                "rows": picked[:self.rows].astype(np.float32)}


def _counters(sched) -> dict:
    return {"useful_steps": sched.total_useful_steps,
            "slot_steps": sched.total_slot_steps}


def run_window(sched, cell, vocab: int, seed: int, seconds: float,
               compiles: CompileCount, trace_dir: Optional[str] = None
               ) -> Window:
    mix = cell.traffic
    traffic.check(mix)
    closed = mix["arrivals"] == "backlog"
    depth = int(mix.get("queue_per_slot", 2)) * sched.slots
    due_t = None if closed else traffic.schedule(mix, seed, seconds)
    sampler = Sampler(cell.check, seed)
    w = Window()
    index: Dict[int, int] = {}        # uid -> request index
    tracing = trace_dir is not None
    t_trace = (float(cell.trace.get("start_s", 0.3 * seconds)),
               float(cell.trace.get("seconds", min(5.0, 0.4 * seconds))))
    span = (jax.profiler.TraceAnnotation if tracing
            else (lambda _: contextlib.nullcontext()))
    c0 = compiles.n
    nxt = 0
    w.counters["start"] = _counters(sched)
    t0 = time.perf_counter()

    def submit(i: int, t_due: float) -> None:
        uid = sched.submit(traffic.prompt(mix, vocab, seed, i))
        index[uid] = i
        w.due[uid] = t_due

    def collect(done, t_ret: float, open_window: bool) -> None:
        for c in done:
            i = index[c.uid]
            w.done[c.uid], w.status[c.uid] = t_ret, c.status
            if open_window and closed:
                w.in_window.append(c.uid)
            if sampler.wants(i, len(w.samples)):
                w.samples.append(sampler.keep(
                    i, traffic.prompt(mix, vocab, seed, i), c))

    # a backlog completes its requests a pool at a time, so its window
    # closes at the first step after ``seconds`` that hands some back: the
    # rate then counts whole refills, not where a fixed end cuts one
    state, closing = "before", False
    while True:
        now = time.perf_counter() - t0
        if now >= seconds and (closing or not closed):
            break
        if tracing and state == "before" and now >= t_trace[0]:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            w.traced = {"done": []}
            state = "on"
        elif state == "on" and now >= sum(t_trace):
            jax.profiler.stop_trace()
            state = "after"
        t_sub = time.perf_counter()
        with span("bench.submit"):
            if closed:
                while len(sched) < depth:
                    submit(nxt, now)
                    nxt += 1
            else:
                while nxt < len(due_t) and due_t[nxt] <= now:
                    submit(nxt, float(due_t[nxt]))
                    w.late_s.append(now - float(due_t[nxt]))
                    nxt += 1
        if not sched.pending:
            wake = due_t[nxt] if nxt < len(due_t) else seconds
            time.sleep(max(0.0, min(wake, seconds) - now))
            continue
        t_step = time.perf_counter()
        with span("bench.step"):
            done = sched.step()
        t_ret = time.perf_counter() - t0
        w.host_s["submit"] += t_step - t_sub
        w.host_s["step"] += t_ret + t0 - t_step
        w.longest_s["step"] = max(w.longest_s["step"], t_ret + t0 - t_step)
        closing = closing or (t_ret >= seconds and bool(done))
        w.queue.append((t_ret, sched.pending))
        with span("bench.collect"):
            collect(done, t_ret, True)
            if state == "on":
                w.traced["done"] += [(c.status, c.nfe) for c in done]
            del done
        t_col = time.perf_counter() - t0 - t_ret
        w.host_s["collect"] += t_col
        w.longest_s["collect"] = max(w.longest_s["collect"], t_col)
    w.seconds = w.queue[-1][0] if closing else time.perf_counter() - t0
    w.compiles = compiles.n - c0
    w.counters["end"] = _counters(sched)
    if state == "on":
        jax.profiler.stop_trace()
    if not closed:
        # every request due in the window completes, or counts as late
        # without end: no new arrivals, step until done or the drain limit
        for i in range(nxt, len(due_t)):
            submit(i, float(due_t[i]))
        w.in_window = list(w.due)
        limit = w.seconds + float(mix["drain_limit_s"])
        while sched.pending and time.perf_counter() - t0 < limit:
            done = sched.step()
            collect(done, time.perf_counter() - t0, False)
    return w


def end_to_end(w: Window, mix: dict) -> Dict[str, float]:
    ok = [u for u in w.in_window if w.status.get(u) == "ok"
          and w.done[u] <= w.seconds]
    out = {"tokens_per_s": len(ok) * int(mix["prompt_len"]) / w.seconds}
    if mix["arrivals"] != "backlog":
        lat = np.asarray([(w.done[u] - w.due[u]) * 1e3
                          if w.status.get(u) == "ok" else np.inf
                          for u in w.in_window])
        out["p50_latency_ms"] = float(np.percentile(lat, 50))
        out["p95_latency_ms"] = float(np.percentile(lat, 95))
    return out


def pending_at(w: Window, frac: float) -> int:
    """Requests queued or in flight at the first tick after ``frac`` of
    the window: a queue that grows over the window is above capacity."""
    return next((n for t, n in w.queue if t >= frac * w.seconds),
                w.queue[-1][1] if w.queue else 0)


def memory_peak() -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())

"""Multi-rate serving engine: error-controlled per-request step sizes.

This is where the repo's batching/eps policy lives (launch/serve.py is the
CLI over it). The loop the paper's pareto pitch implies, end to end:

    submit(x) -> request queue
        -> probe: one cheap depth-field step per request
           (core/controllers.py picks a per-sample mesh length K; the
           probe's dz = f(s0, z0) is kept and reused as stage 0 of the
           solve, so probing costs one fewer NFE than it evaluates)
        -> bucket snap: clamp K to the configured serving buckets — a
           PACKING POLICY (bounds masked-step waste and the number of
           (shape, k_max) jit cells), NOT a kernel-eligibility rule
        -> pack same-shape requests into batches, sorted by K so batches
           stay as K-pure as the traffic allows (leftovers mix freely)
        -> drive each batch through ONE masked multi-rate solve
           (``Integrator.solve_multirate``): per-sample eps and the
           mesh-length row are TRACED operands of the runtime-eps fused
           kernel, so a mixed-K batch runs fused end to end and a given
           (shape, k_max) cell never recompiles across bucket mixes
        -> Completed{outputs, K, nfe, err_probe} per request

Hot (easy) requests integrate in 2-4 NFEs; hard ones get 8-16. Per-request
NFE accounting includes the probe cost (minus the reused stage), so
reported pareto points are honest.

The engine is generic over a ``DepthModel`` adapter (embed -> field ->
readout); ``lm_depth_model`` serves the continuous-depth LM
(models/cdepth.py) and ``node_depth_model`` any ``NeuralODE`` (the paper's
image classifiers).

This drain loop is the BATCH-JOB serving shape: ``step()`` probes, packs,
and solves everything queued to completion before admitting new work. The
streaming shape — depth-continuous batching, where finished slots retire
and refill *between segments* of the solve — lives in
``launch/scheduler.py`` (InflightScheduler over
``Integrator.solve_segment``), reusing this module's controller policy
(``make_controller``), bucket snap, and ``DepthModel`` adapters; identical
arrival traces replay against both via ``launch/workload.py``.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import ArchConfig
from repro.core.controllers import (
    EmbeddedErrorController, FixedController, HypersolverResidualController,
    TierRouter,
)
from repro.core.integrate import Integrator, OneTimeWarning
from repro.models.cdepth import lm_g_init, lm_integrator
from repro.models.lm import init_lm_cache, lm_decode_step, lm_prefill


# ----------------------------------------------------------- discrete path ----

def greedy_generate(params, cfg, prompt, gen_len: int, jit_step=None):
    """Standard cached decode; prompt: (B, P) int32. Prefill is a single
    batched forward (one compiled scan over the prompt, models/lm.py),
    then token-by-token greedy decode."""
    B, P = prompt.shape
    caches = init_lm_cache(cfg, B, P + gen_len)
    step = jit_step or jax.jit(
        lambda p, t, c, i: lm_decode_step(p, cfg, t, c, i))
    prefill = jax.jit(lambda p, toks, c: lm_prefill(p, cfg, toks, c))
    logits, caches = prefill(params, prompt, caches)
    out = [jnp.argmax(logits, -1).astype(jnp.int32)]
    for t in range(P, P + gen_len - 1):
        logits, caches = step(params, out[-1], caches,
                              jnp.asarray(t, jnp.int32))
        out.append(jnp.argmax(logits, -1).astype(jnp.int32))
    return jnp.stack(out, axis=1)


# -------------------------------------------------------------- g loading ----

def load_g_params(path: str, cfg: ArchConfig, rank: int = 32):
    """Restore a trained LM hypersolver correction from a CheckpointManager
    directory (the --g-ckpt CLI flag)."""
    cm = CheckpointManager(path)
    step = cm.latest_step()
    if step is None:
        raise FileNotFoundError(f"no checkpoint under {path!r}")
    template = lm_g_init(jax.random.PRNGKey(0), cfg, rank=rank,
                         param_dtype=jnp.float32)
    return cm.restore(step, jax.eval_shape(lambda: template))


def load_flow_params(path: str, cfg: ArchConfig, rank: int = 64):
    """Restore a trained LM flow head (core/flowhead.py) from a
    CheckpointManager directory (the --flow-ckpt CLI flag)."""
    from repro.models.cdepth import lm_flow_init
    cm = CheckpointManager(path)
    step = cm.latest_step()
    if step is None:
        raise FileNotFoundError(f"no checkpoint under {path!r}")
    template = lm_flow_init(jax.random.PRNGKey(0), cfg, rank=rank,
                            param_dtype=jnp.float32)
    return cm.restore(step, jax.eval_shape(lambda: template))


# ---------------------------------------------------------- model adapters ----

@dataclasses.dataclass(frozen=True)
class DepthModel:
    """What the engine needs to serve a continuous-depth model.

    ``embed(params, x)`` lifts a request batch to the ODE state z0;
    ``field_of(params, x)`` builds the vector field over any
    conditioning; ``readout(params, x, zT)`` maps the terminal state to
    outputs (logits). ``params`` is the model's weight pytree: every
    serving jit cell takes it as a traced, non-donated operand, so no
    cell compiles the weights in as constants (at published LM widths
    those constants would exceed what one serialized program may hold).
    ``integ`` is the serving Integrator (base tableau + optional
    correction g).

    Two ways to carry a hypersolver correction:

    * **closure** — ``integ.g`` closes over its parameters. Zero extra
      plumbing, but the params are constants of every jit cell: swapping
      them forces a retrace of every compiled probe/segment/solve.
    * **parametric** — ``g_apply(gp, eps, s, z, dz)`` plus an initial
      ``g_params`` pytree. The serving loops then thread ``gp`` through
      their jit cells as a TRACED, non-donated input, so replacing it
      with a pytree of identical treedef/shapes/dtypes
      (``hot_swap_g``) reuses every compilation — the params-are-inputs
      invariant the online refinery's no-retrace hot-swap rests on
      (launch/refinery.py; docs/architecture.md "the refinery layer").
      ``integ.g`` must be None on this path.

    ``flow_apply``/``flow_params`` carry the OPTIONAL K=0 flow tier
    (core/flowhead.py): ``flow_apply(fp, eps, s, z, dz) -> z(s + eps)``
    is a learned solution operator — one eval, no solver — that the
    serving loops route probe-easy requests to when
    ``EngineConfig.flow_threshold > 0``. Flow params follow the same
    params-are-inputs contract as g (traced cell operands,
    ``hot_swap_flow``)."""

    embed: Callable[[Any, Any], Any]
    field_of: Callable[[Any, Any], Callable]
    readout: Callable[[Any, Any, Any], Any]
    integ: Integrator
    params: Any = None                   # model weights, a cell operand
    span: Tuple[float, float] = (0.0, 1.0)
    g_apply: Optional[Callable] = None   # g_apply(gp, eps, s, z, dz)
    g_params: Any = None                 # initial swappable params
    flow_apply: Optional[Callable] = None  # flow_apply(fp, eps, s, z, dz)
    flow_params: Any = None              # initial swappable flow params


def bound_integrator(model: DepthModel, gp=None) -> Integrator:
    """``model.integ`` with the parametric correction bound over ``gp``
    (defaulting to the model's initial params). Used wherever an
    Integrator-with-g is needed OUTSIDE a serving jit cell — controller
    policy checks, shadow scoring, offline evaluation. Inside the cells
    the loops bind g themselves so ``gp`` stays a traced operand."""
    if model.g_apply is None:
        return model.integ
    ga = model.g_apply
    if gp is None:
        gp = model.g_params
    return dataclasses.replace(
        model.integ, g=lambda e, s, z, dz: ga(gp, e, s, z, dz))


def validate_g_swap(current, new, label: str = "hot_swap_g") -> None:
    """Refuse a hot-swap that would retrace: the incoming params must
    match the resident pytree leaf for leaf (treedef, shapes, dtypes) —
    the exact condition under which jit reuses the compiled cells that
    took ``current`` as an input. Shared by the engine's and scheduler's
    ``hot_swap_g`` AND ``hot_swap_flow`` (``label`` names the caller in
    the error)."""
    t_cur, d_cur = jax.tree_util.tree_flatten(current)
    t_new, d_new = jax.tree_util.tree_flatten(new)
    if d_cur != d_new:
        raise ValueError(
            f"{label}: params treedef mismatch ({d_new} vs resident "
            f"{d_cur}) — a swap must preserve the pytree structure or "
            "every serving cell would retrace")
    for i, (c, n) in enumerate(zip(t_cur, t_new)):
        cs, cd = jnp.shape(c), jnp.asarray(c).dtype
        ns, nd = jnp.shape(n), jnp.asarray(n).dtype
        if cs != ns or cd != nd:
            raise ValueError(
                f"{label}: leaf {i} is {ns}/{nd}, resident is "
                f"{cs}/{cd} — shapes and dtypes must match exactly "
                "(the no-retrace contract)")


def lm_depth_model(params, cfg: ArchConfig, solver: str = "euler",
                   g_params: Any = None, fused: bool = False, *,
                   refinable: bool = False, rank: int = 32,
                   flow_params: Any = None) -> DepthModel:
    """The unified LM's depth ODE (models/cdepth.py) as a servable model.

    ``refinable=True`` carries the correction on the PARAMETRIC path
    (``g_apply``/``g_params`` as traced cell inputs) instead of baking
    it into ``integ.g`` — required for the online refinery's no-retrace
    hot-swap. Without a trained ``g_params`` it starts from a fresh
    zero-readout init (g == 0 exactly, pure base solver) that the
    refinery then fits from live traffic.

    ``flow_params`` (a trained ``lm_flow_init``-shaped pytree, e.g. from
    ``load_flow_params``) attaches the K=0 flow tier: the model carries
    ``flow_apply``/``flow_params`` on the same parametric contract, and
    the serving loops route probe-easy requests to it when
    ``EngineConfig.flow_threshold > 0``."""
    from repro.models.cdepth import (
        apply_tail, depth_field, lm_flow_apply, lm_g_apply,
    )
    from repro.models.lm import _embed

    field = depth_field(cfg)
    kw = {}
    if refinable:
        base = solver[len("hyper_"):] if solver.startswith("hyper_") \
            else solver
        if g_params is None:
            g_params = lm_g_init(jax.random.PRNGKey(0), cfg, rank=rank,
                                 param_dtype=jnp.float32)
        integ = lm_integrator(base, None, fused=fused)
        kw = dict(
            g_apply=lambda gp, eps, s, z, dz:
                lm_g_apply(gp, eps, s, None, z, dz),
            g_params=g_params)
    else:
        integ = lm_integrator(solver, g_params, fused=fused)
    if flow_params is not None:
        order = integ.order
        kw.update(
            flow_apply=lambda fp, eps, s, z, dz:
                lm_flow_apply(fp, eps, s, z, dz, order=order),
            flow_params=flow_params)
    return DepthModel(
        embed=lambda p, toks: _embed(p, cfg, toks),
        field_of=lambda p, toks: partial(field, p),
        readout=lambda p, toks, h: apply_tail(p, cfg, h),
        integ=integ,
        params=params,
        **kw,
    )


def node_depth_model(node, params, solver: str = "euler",
                     g_apply: Any = None, g_params: Any = None,
                     fused: bool = False) -> DepthModel:
    """Any ``NeuralODE`` (core/neural_ode.py) as a servable model — e.g.
    the paper's image classifiers (models/conv_node.py). ``solver`` may
    carry a ``hyper_`` prefix (requires g_apply/g_params). ``g_apply`` gets
    x=None: conditioning-dependent corrections need a custom adapter."""
    from repro.core.train import make_integrator

    if solver.startswith("hyper_"):
        if g_apply is None:
            raise ValueError(
                f"solver {solver!r} needs a correction: pass g_apply/"
                "g_params (a hyper solver silently downgraded to its base "
                "would misreport benchmark numbers)")
        base = solver[len("hyper_"):]
    else:
        base = solver
    return DepthModel(
        embed=node.hx_apply,
        field_of=node.field,
        readout=lambda p, x, zT: node.hy_apply(p, zT),
        integ=make_integrator(base, g_apply, g_params, None, fused=fused),
        params=params,
        span=tuple(node.s_span),
    )


# ------------------------------------------------------------ bucket policy ----

# bucket-overflow snap latch: one shared OneTimeWarning mechanism with
# the fused-fallback warning (tests re-arm both per test via conftest)
_snap_overflow = OneTimeWarning()


def reset_snap_overflow_warning() -> None:
    """Re-arm the one-time bucket-overflow RuntimeWarning (test isolation)."""
    _snap_overflow.reset()


# non-finite probe latch: a NaN/Inf probe error used to select k_max
# SILENTLY (core/controllers.py::mesh_for_tolerance clamps inside jit,
# where it cannot warn) — both serving loops now screen the materialized
# error row host-side, warn once, and thread the count into
# StepReport/TickReport.probe_nonfinite; the request itself is handled
# by the quarantine layer (its state is non-finite from step one, so the
# segment cell's nonfinite meta row force-retires it).
_probe_nonfinite = OneTimeWarning()


def reset_probe_nonfinite_warning() -> None:
    """Re-arm the one-time non-finite-probe RuntimeWarning (test
    isolation)."""
    _probe_nonfinite.reset()


def screen_probe_errors(errs: np.ndarray) -> int:
    """Count non-finite probe errors in a materialized error row and
    warn once. ``mesh_for_tolerance`` already routes such requests to
    ``k_max`` (the conservative mesh), but inside jit it cannot signal —
    this host-side screen is where the silent clamp becomes observable.
    Shared by MultiRateEngine.step and the scheduler's admission."""
    n_bad = int((~np.isfinite(np.asarray(errs))).sum())
    if n_bad:
        _probe_nonfinite.warn(
            f"non-finite probe error for {n_bad} request(s): the probe "
            "step itself blew up, so the controller assigned k_max (the "
            "finest mesh). The solve is likely to diverge too — the "
            "non-finite quarantine will force-retire it with "
            "status='diverged'.", stacklevel=3)
    return n_bad


def next_bucket_above(K: int, buckets: Sequence[int]) -> Optional[int]:
    """The finest configured bucket strictly greater than ``K`` — the
    retry ladder's escalation rule (a diverged K-bucket solve retries at
    the next-finer mesh). None when ``K`` is already the top bucket."""
    for b in sorted(buckets):
        if b > K:
            return int(b)
    return None


def snap_to_buckets(Ks: np.ndarray, buckets: Sequence[int]) -> np.ndarray:
    """Smallest configured bucket >= K (largest bucket when K overshoots,
    with a one-time warning — that clamp integrates COARSER than asked).

    Snapping up, never down — except at the top: a request is only ever
    integrated at least as finely as its controller asked for, unless its
    K exceeds ``buckets[-1]`` entirely, in which case it clamps down to
    ``buckets[-1]`` (the warning latch flags the quality degradation once).
    Since the runtime-eps kernel fuses any K mix, snapping exists purely to
    bound masked-step waste and the set of (shape, k_max) jit cells — not
    to make batches kernel-eligible."""
    buckets = np.asarray(sorted(buckets), np.int32)
    Ks = np.asarray(Ks, np.int32)
    if Ks.size and int(Ks.max()) > int(buckets[-1]):
        _snap_overflow.warn(
            f"snap_to_buckets: probed K={int(Ks.max())} exceeds the "
            f"largest configured bucket {int(buckets[-1])}; clamping down "
            "to it. The request will integrate more coarsely than its "
            "controller asked for — widen the bucket set (or raise tol) "
            "if this is steady-state traffic.", stacklevel=3)
    idx = np.searchsorted(buckets, Ks, side="left")
    return buckets[np.minimum(idx, len(buckets) - 1)]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Batching/eps policy knobs for the multi-rate engine."""

    buckets: Tuple[int, ...] = (2, 4, 8, 16)
    tol: float = 1e-2             # target local-error tolerance for probes
    max_batch: int = 8            # max requests packed into one bucket batch
    solver: str = "euler"         # base tableau; "hyper_*" pairs it with g
    controller: str = "auto"      # auto | residual | embedded | fixed
    fixed_K: int = 0              # mesh length when controller == "fixed"
    fused: bool = False           # route batch solves through the kernel
    #                               (runtime-eps: any K mix fuses)
    flow_threshold: float = 0.0   # K=0 flow tier confidence fraction:
    #                               route iff probe err <= this * tol
    #                               (0 disables the tier entirely)

    def __post_init__(self):
        assert self.buckets == tuple(sorted(self.buckets)), self.buckets
        if not (0.0 <= self.flow_threshold <= 1.0):
            raise ValueError(
                f"flow_threshold={self.flow_threshold}: expected a "
                "confidence fraction in [0, 1] (core/controllers.py::"
                "TierRouter) — the flow tier only serves requests whose "
                "probe error is confidently below tol")


def prepare_model(model: DepthModel, ecfg: "EngineConfig") -> DepthModel:
    """Shared serving-loop model vetting: promote the integrator onto the
    fused kernel path when the config asks for it, and refuse a hyper_*
    solver with no correction bound (a silent downgrade to the base
    tableau would misreport every NFE/agreement number downstream). Both
    MultiRateEngine and InflightScheduler construct through here, so the
    two loops cannot drift on eligibility policy."""
    if ecfg.fused and not model.integ.fused:
        model = dataclasses.replace(
            model, integ=dataclasses.replace(model.integ, fused=True))
    if model.g_apply is not None and model.integ.g is not None:
        raise ValueError(
            "DepthModel carries BOTH a closure correction (integ.g) and "
            "a parametric one (g_apply); pick one — a cell binding both "
            "would apply g twice")
    if ecfg.solver.startswith("hyper_") and model.integ.g is None \
            and model.g_apply is None:
        raise ValueError(
            f"solver {ecfg.solver!r} needs a correction: build the "
            "DepthModel with g_params (serve CLI: --g-ckpt)")
    if ecfg.flow_threshold > 0:
        if model.flow_apply is None:
            raise ValueError(
                f"flow_threshold={ecfg.flow_threshold} routes easy "
                "requests to the K=0 flow tier, but the DepthModel "
                "carries no flow head: build it with flow_apply/"
                "flow_params (serve CLI: --flow-ckpt)")
        if ecfg.controller == "fixed":
            raise ValueError(
                "flow_threshold > 0 needs a probing controller — the "
                "flow tier routes off the admission probe's difficulty "
                "estimate, which controller='fixed' never computes")
    return model


def make_controller(integ: Integrator, ecfg: "EngineConfig"):
    """Controller selection shared by the drain engine and the in-flight
    scheduler (launch/scheduler.py): same knobs -> same per-request K
    policy, so the two serving loops are comparable request-for-request."""
    kind = ecfg.controller
    if kind == "auto":
        kind = "residual" if integ.g is not None else "embedded"
    k_min, k_max = min(ecfg.buckets), max(ecfg.buckets)
    if kind == "fixed":
        K = ecfg.fixed_K or k_max
        assert K <= k_max, (
            f"fixed_K={K} exceeds the largest bucket {k_max}; "
            "snap_to_buckets never snaps down — widen buckets")
        return FixedController(K=K)
    if kind == "residual":
        return HypersolverResidualController(
            tol=ecfg.tol, k_min=k_min, k_max=k_max)
    if kind == "embedded":
        return EmbeddedErrorController(
            tol=ecfg.tol, k_min=k_min, k_max=k_max)
    raise ValueError(f"unknown controller {kind!r}")


def probe_net_nfe(controller) -> int:
    """Per-request probe cost net of the reused first stage (the probe's
    dz0 substitutes for stage 0 of the solve, so one eval is free)."""
    raw = getattr(controller, "probe_nfe", 0)
    return max(raw - 1, 0) if raw else 0


@dataclasses.dataclass(frozen=True)
class StepReport:
    """Virtual-cost accounting for one engine drain, priced by the
    engine's cost oracle (``launch/oracle.py``). Under the default
    ``SequentialEvalOracle`` the unit is SEQUENTIAL vector-field
    evaluations (the unit a batch-parallel accelerator serializes on):
    a K-step scan of an s-stage tableau costs s*K regardless of batch
    width, a probe costs its probe_nfe. The trace replayer
    (launch/workload.py) uses this to compare the drain loop and the
    in-flight scheduler on identical arrival traces.

    ``finish_offset`` maps uid -> cost offset (from drain start) at which
    its batch's solve completed — requests in the first bucket batch of a
    drain finish before the last batch does."""

    cost: float = 0.0                 # total sequential evals this drain
    probe_cost: float = 0.0           # sequential evals spent probing
    useful_steps: int = 0             # sum of per-sample K over served rows
    total_steps: int = 0              # sum of batch_rows * k_max over batches
    batches: int = 0
    probe_nonfinite: int = 0          # non-finite probe errors this drain
    finish_offset: Dict[int, float] = dataclasses.field(default_factory=dict)
    flow_served: int = 0              # requests completed on the K=0 tier
    escalated: int = 0                # flow failures requeued to the ladder

    @property
    def waste_steps(self) -> int:
        """Masked sample-steps: rows scanned past their own K_i."""
        return self.total_steps - self.useful_steps


# terminal request statuses both serving loops stamp (docs/serving.md
# "Failure semantics" carries the operator-facing glossary; the docs
# gate in tests/test_docs.py asserts against THIS tuple):
#   ok        — completed its mesh, first attempt
#   retried   — completed after >= 1 quarantine/eviction retry
#   diverged  — non-finite state, retry ladder exhausted (best-effort
#               outputs: the poisoned partial readout)
#   deadline  — evicted past its deadline (best-effort partial readout,
#               or none if it expired while still queued)
#   shed      — refused at admission by the overload policy (no outputs)
#   escalated — completed on the K-bucket ladder after its K=0 flow-tier
#               eval came back non-finite (real outputs; the flow
#               attempt's nfe is billed into the record)
STATUSES = ("ok", "retried", "diverged", "deadline", "shed", "escalated")


class QueueFull(RuntimeError):
    """Bounded admission queue is full under overload_policy='block'.
    Callers back off and resubmit (``can_submit()`` is the non-raising
    probe; launch/workload.py's replay drivers defer the arrival)."""


@dataclasses.dataclass(frozen=True)
class Request:
    uid: int
    x: np.ndarray                 # one request's input (no batch axis)
    deadline: Optional[float] = None  # oracle-clock deadline (None = none)
    attempts: int = 0             # completed (failed) serve attempts so far
    K_floor: int = 0              # retry ladder: minimum bucket on re-probe
    escalated: bool = False       # a failed K=0 flow eval sent it here


@dataclasses.dataclass(frozen=True)
class Completed:
    uid: int
    outputs: np.ndarray           # readout of the terminal state (e.g. logits)
    K: int                        # bucket mesh length actually used
    nfe: int                      # per-request NFE, probe included
    err_probe: float              # controller's local-error estimate
    fused_kernel: bool            # Pallas fused path in play for the solve
    status: str = "ok"            # terminal status (STATUSES)


class MultiRateEngine:
    """Request-queue engine serving continuous-depth models at per-request
    rates. Heavy lifting is jitted and cached per request shape for probes
    and per (shape, K) for bucket solves, so a steady-state traffic mix
    compiles once per cell."""

    def __init__(self, model: DepthModel, engine_cfg: EngineConfig,
                 oracle=None, *, queue_cap: Optional[int] = None,
                 overload_policy: str = "shed", retry=None,
                 fault_injector=None, ledger=None):
        from repro.distributed.fault import RetryPolicy
        from repro.launch.oracle import SequentialEvalOracle
        if overload_policy not in ("shed", "degrade", "block"):
            raise ValueError(f"unknown overload_policy {overload_policy!r} "
                             "(shed | degrade | block)")
        if queue_cap is not None and queue_cap < 1:
            raise ValueError(f"queue_cap must be >= 1, got {queue_cap} "
                             "(a zero-width queue can never admit)")
        self.model = prepare_model(model, engine_cfg)
        self.ecfg = engine_cfg
        # model weights: one device-resident copy, passed into every jit
        # cell as a traced operand (never a compiled-in constant)
        self.params = jax.tree_util.tree_map(jnp.asarray, self.model.params)
        # controller policy decides off the BOUND integrator (a parametric
        # g counts as a correction for controller="auto"); the cells
        # re-bind g over the traced gp operand themselves
        self.controller = make_controller(
            bound_integrator(self.model), self.ecfg)
        # hot-swappable correction params: host-held, passed into every
        # parametric jit cell at CALL time — hot_swap_g replaces them
        # between drains with zero retraces (validate_g_swap)
        self.g_params = None if self.model.g_apply is None else \
            jax.tree_util.tree_map(jnp.asarray, self.model.g_params)
        # the K=0 flow tier's swappable params + router policy; None/None
        # when the tier is disabled (flow_threshold == 0), in which case
        # NO flow code runs — the bitwise-parity guarantee vs pre-flow
        self.flow_params = None if self.model.flow_apply is None else \
            jax.tree_util.tree_map(jnp.asarray, self.model.flow_params)
        self.router = TierRouter(
            flow_threshold=engine_cfg.flow_threshold) \
            if engine_cfg.flow_threshold > 0 else None
        self.ledger = ledger   # optional ResidualLedger (launch/refinery)
        self.oracle = oracle or SequentialEvalOracle()
        self.queue_cap = queue_cap
        self.overload_policy = overload_policy
        self.retry = retry or RetryPolicy()
        self.fault_injector = fault_injector
        self._queue: deque = deque()
        self._uid = 0
        self._shed: List[Completed] = []
        self._nfe_extra: Dict[int, int] = {}   # failed attempts' NFE per uid
        self._probe_fns: Dict[Tuple, Any] = {}
        self._solve_fns: Dict[Tuple, Any] = {}
        self._embed_fns: Dict[Tuple, Any] = {}
        self._flow_fns: Dict[Tuple, Any] = {}
        self.last_report = StepReport()

    # ---------------------------------------------------------- policy ----
    @property
    def probe_nfe(self) -> int:
        """Probe cost per request, net of the reused first stage."""
        return probe_net_nfe(self.controller)

    def fused_in_play(self, z0=None) -> bool:
        """Kernel eligibility is K-independent now (runtime-eps kernel):
        only the integrator's fused flag and the state dtypes matter —
        pass the embedded state (or its eval_shape) to vet the latter."""
        return self.model.integ.fused_available(z=z0)

    def nfe_of(self, K: int) -> int:
        """Per-request NFE for a bucket-K solve, probe included (the solve
        reuses the probe's first stage, so one eval is not double-counted)."""
        return self.probe_nfe + self.model.integ.tableau.stages * K

    @property
    def nfe_flow(self) -> int:
        """Per-request NFE on the K=0 flow tier: the probe's RAW field
        evals, nothing else. ``probe_nfe`` nets out the stage the solve
        reuses; on the flow tier that same stage is consumed by the flow
        combine's ``eps*dz`` term, so it is billed back here (+1) and the
        total is probe evals + ZERO solver steps — strictly below
        ``nfe_of(k_min)`` for every controller."""
        return self.probe_nfe + 1

    def probe(self, xs):
        """Probe a request batch without serving it: returns (raw per-
        sample K before bucket snapping, per-sample error estimate)."""
        xs = np.asarray(xs)
        Ks, errs, _, _ = self._probe_fn(xs.shape[1:])(
            self.params, jnp.asarray(xs), *self._g_args())
        return np.asarray(Ks), np.asarray(errs)

    # ----------------------------------------------------------- queue ----
    def can_submit(self) -> bool:
        """False iff the bounded queue is full under ``block`` (the only
        policy that pushes backpressure to the caller; ``shed`` admits
        the submit and refuses the request, ``degrade`` admits and caps
        K under pressure)."""
        return not (self.queue_cap is not None
                    and self.overload_policy == "block"
                    and len(self._queue) >= self.queue_cap)

    def submit(self, x, deadline: Optional[float] = None) -> int:
        """Queue a request. ``deadline`` is an absolute time on the
        replay clock (``step(now=...)``); requests past it at drain
        start are dropped with ``status="deadline"``. A full bounded
        queue sheds (terminal ``status="shed"``, surfaced by the next
        ``step()``) or raises ``QueueFull`` under ``block``."""
        if self.queue_cap is not None \
                and len(self._queue) >= self.queue_cap:
            if self.overload_policy == "block":
                raise QueueFull(
                    f"admission queue at cap {self.queue_cap} under "
                    "overload_policy='block'; poll can_submit() and "
                    "resubmit")
            if self.overload_policy == "shed":
                self._uid += 1
                self._shed.append(Completed(
                    uid=self._uid, outputs=None, K=0, nfe=0,
                    err_probe=0.0, fused_kernel=False, status="shed"))
                return self._uid
            # degrade: admit past the cap; the drain caps K one bucket
            # down while the queue stays over pressure (see step())
        self._uid += 1
        self._queue.append(Request(uid=self._uid, x=np.asarray(x),
                                   deadline=deadline))
        return self._uid

    def __len__(self) -> int:
        # shed records count until a step() surfaces them, so drive
        # loops (run / replay_engine) never exit with terminals unread
        return len(self._queue) + len(self._shed)

    # ------------------------------------------------------- jit cells ----
    def _g_args(self) -> Tuple:
        """The trailing cell operands for the hot-swappable correction:
        ``(g_params,)`` on a parametric model, ``()`` otherwise. Read at
        CALL time so a hot_swap_g lands on the very next drain."""
        return () if self.model.g_apply is None else (self.g_params,)

    def _probe_fn(self, shape):
        if shape not in self._probe_fns:
            m, ctrl = self.model, self.controller
            parametric = m.g_apply is not None

            @jax.jit
            def probe(params, x, *gps):
                # parametric g rides as a traced operand (gps = (gp,)),
                # so swapped params reuse this compilation
                integ = bound_integrator(m, gps[0]) if parametric \
                    else m.integ
                z0 = m.embed(params, x)
                p = ctrl.select(integ, m.field_of(params, x), z0, m.span)
                return p.K, p.err, z0, p.dz0

            self._probe_fns[shape] = probe
        return self._probe_fns[shape]

    def _embed_fn(self, shape):
        """Embed-only cell for the ledger-capture path under a fixed
        controller (no probe, so no z0 to reuse). Capture-only state:
        never fed to the solve, never priced by the oracle."""
        if shape not in self._embed_fns:
            self._embed_fns[shape] = jax.jit(self.model.embed)
        return self._embed_fns[shape]

    def _flow_args(self) -> Tuple:
        """Trailing cell operands for the hot-swappable flow head:
        ``(flow_params,)`` when the model carries one, ``()`` otherwise.
        Read at CALL time so a hot_swap_flow lands on the next drain."""
        return () if self.model.flow_apply is None else (self.flow_params,)

    def _flow_fn(self, shape):
        """The K=0 tier's jit cell: one flow-head eval + readout over the
        probe's already-materialized (z0, dz0). Variable-width like the
        drain's solve batches; flow params ride as a traced trailing
        operand (the params-are-inputs invariant, same as g)."""
        if shape not in self._flow_fns:
            m = self.model
            h = m.span[1] - m.span[0]
            s0 = m.span[0]

            @jax.jit
            def flow(params, x, z0, dz0, *fps):
                return m.readout(params, x,
                                 m.flow_apply(fps[0], h, s0, z0, dz0))

            self._flow_fns[shape] = flow
        return self._flow_fns[shape]

    def _solve_fn(self, shape, k_max: int):
        key = (shape, k_max)
        if key not in self._solve_fns:
            m = self.model
            parametric = m.g_apply is not None

            @jax.jit
            def solve(params, x, z0, dz0, Ks, *gps):
                # z0/dz0 come from the probe cell (embed + first stage are
                # not recomputed); the fixed path passes z0=None and
                # embeds here. Ks is a TRACED (B,) row: sample i runs its
                # own eps_i = span / Ks[i] mesh and freezes after Ks[i]
                # steps, so one (shape, k_max) compilation serves every
                # bucket mix and every step size the controller emits.
                # gps, when present, is the hot-swappable correction
                # params pytree — traced, so swaps never retrace.
                integ = bound_integrator(m, gps[0]) if parametric \
                    else m.integ
                if z0 is None:
                    z0 = m.embed(params, x)
                zT = integ.solve_multirate(
                    m.field_of(params, x), z0, m.span, Ks, k_max,
                    first_stage=dz0)
                return m.readout(params, x, zT)

            self._solve_fns[key] = solve
        return self._solve_fns[key]

    # --------------------------------------------------------- hot swap ----
    def hot_swap_g(self, gp):
        """Install new correction params between drains: every cached
        probe/solve cell takes them as a traced input, so the swap
        compiles NOTHING and the next ``step()`` serves with the new g.
        Returns the previous params (the refinery's rollback handle).
        Raises ValueError if the incoming pytree would retrace."""
        if self.model.g_apply is None:
            raise ValueError(
                "hot_swap_g on a non-parametric model: build the "
                "DepthModel with g_apply/g_params (params-are-inputs) "
                "to make the correction swappable")
        gp = jax.tree_util.tree_map(jnp.asarray, gp)
        validate_g_swap(self.g_params, gp)
        old, self.g_params = self.g_params, gp
        return old

    def hot_swap_flow(self, fp):
        """Install new flow-head params between drains — the flow twin of
        ``hot_swap_g``, same zero-retrace contract (the flow cell takes
        them as a traced input). Returns the previous params."""
        if self.model.flow_apply is None:
            raise ValueError(
                "hot_swap_flow on a model with no flow head: build the "
                "DepthModel with flow_apply/flow_params (core/flowhead."
                "py) to make the K=0 tier swappable")
        fp = jax.tree_util.tree_map(jnp.asarray, fp)
        validate_g_swap(self.flow_params, fp, label="hot_swap_flow")
        old, self.flow_params = self.flow_params, fp
        return old

    # ------------------------------------------------------------ serve ----
    def step(self, now: float = 0.0) -> List[Completed]:
        """Drain the queue once: probe, bucket, pack, solve. Returns the
        completed requests (order not guaranteed — uid is the join key).
        ``self.last_report`` carries this drain's virtual-cost accounting
        (StepReport) for the trace replayer in launch/workload.py.

        ``now`` is the replay clock at drain start: requests already
        past their deadline drop terminally (``status="deadline"``)
        before any probe is spent on them. Rows whose outputs come back
        non-finite either retry (re-queued at the next-finer bucket,
        served by the NEXT drain, bounded by the RetryPolicy) or return
        best-effort with ``status="diverged"``."""
        done: List[Completed] = list(self._shed)   # surface shed refusals
        self._shed = []
        if not self._queue:
            self.last_report = StepReport(
                finish_offset={c.uid: 0.0 for c in done})
            return done
        stages = self.model.integ.tableau.stages
        cost = probe_cost = 0.0
        useful = total = batches = probe_nonfinite = 0
        flow_served = escalated = 0
        finish_offset: Dict[int, float] = {c.uid: 0.0 for c in done}
        # degrade pressure is measured once per drain, at its start
        degrade = (self.queue_cap is not None
                   and self.overload_policy == "degrade"
                   and len(self._queue) > self.queue_cap)
        pending: List[Request] = []
        while self._queue:
            r = self._queue.popleft()
            if r.deadline is not None and r.deadline < now:
                finish_offset[r.uid] = 0.0
                done.append(Completed(
                    uid=r.uid, outputs=None, K=0,
                    nfe=self._nfe_extra.pop(r.uid, 0), err_probe=0.0,
                    fused_kernel=False, status="deadline"))
                continue
            pending.append(r)
        if not pending:
            self.last_report = StepReport(finish_offset=finish_offset)
            return done
        # group by request shape — each shape is its own jit cell
        by_shape: Dict[Tuple, List[Request]] = {}
        for r in pending:
            by_shape.setdefault(r.x.shape, []).append(r)

        for shape, reqs in by_shape.items():
            rows = [r.x for r in reqs]
            if self.fault_injector is not None:
                rows = [self.fault_injector.corrupt_admission(
                    r.uid, r.attempts, x) for r, x in zip(reqs, rows)]
            xs = np.stack(rows)
            if isinstance(self.controller, FixedController):
                Ks_raw = np.full((len(reqs),), self.controller.K, np.int32)
                errs = np.zeros((len(reqs),), np.float32)
                z0 = dz0 = None
            else:
                Ks_dev, err_dev, z0, dz0 = self._probe_fn(shape)(
                    self.params, jnp.asarray(xs), *self._g_args())
                Ks_raw = np.asarray(Ks_dev)
                errs = np.asarray(err_dev)
                probe_nonfinite += screen_probe_errors(errs)
                p = self.oracle.probe_cost(
                    shape, len(reqs),
                    getattr(self.controller, "probe_nfe", 0))
                probe_cost += p
                cost += p
            Ks = snap_to_buckets(Ks_raw, self.ecfg.buckets)
            if degrade:
                # graceful degradation: every admission in an over-
                # pressure drain serves one bucket coarser than asked —
                # agreement trades off measurably, nothing is refused
                b = np.asarray(sorted(self.ecfg.buckets), np.int32)
                Ks = b[np.maximum(np.searchsorted(b, Ks) - 1, 0)]
            # retry-ladder escalation: a re-queued request never serves
            # below its K_floor (the next-finer bucket than the one that
            # failed)
            floors = np.asarray([r.K_floor for r in reqs], np.int32)
            Ks = np.maximum(Ks, floors)

            if self.ledger is not None:
                # residual-ledger capture (launch/refinery.py): one extra
                # readout per drain, computed from the probe states the
                # cells already materialized at the eps each request will
                # actually integrate at (the fixed path has no probe, so
                # capture embeds its own copy). Rows with a non-finite
                # probe (quarantine-bound) are excluded; capture reads
                # state, never mutates it, and is never priced by the
                # cost oracle — so capture-enabled completions stay
                # bitwise identical to capture-disabled ones.
                span = self.model.span
                z_cap = z0 if z0 is not None else \
                    self._embed_fn(shape)(self.params, jnp.asarray(xs))
                self.ledger.capture(
                    jnp.asarray(xs), z_cap,
                    np.full(len(reqs), span[0], np.float32),
                    ((span[1] - span[0])
                     / Ks.astype(np.float64)).astype(np.float32),
                    keep=np.isfinite(errs))

            # mixed-K packing: sort by K so batches stay as K-pure as the
            # traffic allows (bucket purity bounds masked-step waste), then
            # fill batches of <= max_batch straight through — a batch mixing
            # buckets still solves fused, scanning to its largest K.
            take = lambda tree, sel: None if tree is None else \
                jax.tree_util.tree_map(lambda l: l[sel], tree)
            # vet the actual state dtypes so Completed.fused_kernel is
            # honest; the fixed path has no probe z0, so eval_shape the
            # embedding (dtypes only, no compute)
            z_like = z0 if z0 is not None else jax.eval_shape(
                self.model.embed, self.params,
                jax.ShapeDtypeStruct(xs.shape, xs.dtype))
            fused = self.fused_in_play(z_like)

            # K=0 flow tier (core/flowhead.py): requests whose probe
            # error sits confidently below tol skip the ladder entirely
            # — one flow-head eval + readout, zero solver steps. Tier is
            # a PACKING decision like the buckets: flow rows route to
            # their own per-shape jit cell and are excluded from the
            # pack loop below; nothing about the ladder cells changes.
            # With the tier disabled (router is None) this whole block
            # is a no-op and the drain is bitwise identical to pre-flow.
            flow_sel = np.zeros(len(reqs), bool)
            if self.router is not None and z0 is not None:
                flow_sel = np.asarray(self.router.flow_mask(
                    errs, self.ecfg.tol, floors))
            fidx = np.flatnonzero(flow_sel)
            if len(fidx):
                f_out = np.asarray(self._flow_fn(shape)(
                    self.params, jnp.asarray(xs[fidx]), take(z0, fidx),
                    take(dz0, fidx), *self._flow_args()))
                cost += self.oracle.flow_cost(shape, len(fidx))
                for j, i in enumerate(fidx):
                    r = reqs[i]
                    row = f_out[j]
                    if self.fault_injector is not None:
                        row = self.fault_injector.corrupt_flow_eval(
                            r.uid, r.attempts, row)
                    if not np.isfinite(row).all():
                        # escalation path: the no-solver answer failed —
                        # bill the flow attempt and requeue into the
                        # K-bucket ladder at the coarsest bucket (the
                        # next drain re-probes; K_floor > 0 also bars
                        # re-routing to flow), bounded by the RetryPolicy
                        if self.retry.should_retry(
                                "diverged", r.attempts):
                            self._nfe_extra[r.uid] = (
                                self._nfe_extra.get(r.uid, 0)
                                + self.nfe_flow)
                            self._queue.append(dataclasses.replace(
                                r, attempts=r.attempts + 1,
                                K_floor=min(self.ecfg.buckets),
                                escalated=True))
                            escalated += 1
                            continue
                        finish_offset[r.uid] = cost
                        done.append(Completed(
                            uid=r.uid, outputs=row, K=0,
                            nfe=self.nfe_flow
                            + self._nfe_extra.pop(r.uid, 0),
                            err_probe=float(errs[i]),
                            fused_kernel=False, status="diverged"))
                        continue
                    # flow_mask bars K_floor > 0, so attempts == 0 here
                    finish_offset[r.uid] = cost
                    flow_served += 1
                    done.append(Completed(
                        uid=r.uid, outputs=row, K=0,
                        nfe=self.nfe_flow
                        + self._nfe_extra.pop(r.uid, 0),
                        err_probe=float(errs[i]), fused_kernel=False,
                        status="ok"))

            order = np.argsort(Ks, kind="stable")
            order = order[~flow_sel[order]]
            for lo in range(0, len(order), self.ecfg.max_batch):
                sel = order[lo:lo + self.ecfg.max_batch]
                k_max = int(Ks[sel].max())
                outputs = np.asarray(
                    self._solve_fn(shape, k_max)(
                        self.params, jnp.asarray(xs[sel]), take(z0, sel),
                        take(dz0, sel), jnp.asarray(Ks[sel], jnp.int32),
                        *self._g_args()))
                cost += self.oracle.solve_cost(shape, k_max, len(sel),
                                               stages)
                useful += int(Ks[sel].sum())
                total += len(sel) * k_max
                batches += 1
                # row-wise non-finite screen on the ALREADY-materialized
                # outputs (no extra device transfer): diverged rows
                # climb the retry ladder or return best-effort
                finite = np.isfinite(
                    outputs.reshape(len(sel), -1)).all(axis=1)
                for j, i in enumerate(sel):
                    r, K = reqs[i], int(Ks[i])
                    if not finite[j]:
                        # next-finer bucket; at the top (where a poisoned
                        # probe's k_max clamp lands) one clean re-run at
                        # the same bucket, bounded by the RetryPolicy
                        nxt = next_bucket_above(K, self.ecfg.buckets) or K
                        if self.retry.should_retry(
                                "diverged", r.attempts):
                            self._nfe_extra[r.uid] = (
                                self._nfe_extra.get(r.uid, 0)
                                + self.nfe_of(K))
                            self._queue.append(dataclasses.replace(
                                r, attempts=r.attempts + 1, K_floor=nxt))
                            continue     # served by the next drain
                        status = "diverged"
                    else:
                        status = "ok" if r.attempts == 0 else (
                            "escalated" if r.escalated else "retried")
                    finish_offset[r.uid] = cost
                    done.append(Completed(
                        uid=r.uid, outputs=outputs[j], K=K,
                        nfe=self.nfe_of(K)
                        + self._nfe_extra.pop(r.uid, 0),
                        err_probe=float(errs[i]), fused_kernel=fused,
                        status=status))
        self.last_report = StepReport(
            cost=cost, probe_cost=probe_cost, useful_steps=useful,
            total_steps=total, batches=batches,
            probe_nonfinite=probe_nonfinite, finish_offset=finish_offset,
            flow_served=flow_served, escalated=escalated)
        return done

    def run(self, xs) -> List[Completed]:
        """Convenience: submit a batch (leading axis = requests) and drain
        to completion, returning results ordered by submission. Loops
        until every uid is terminal — a retried request drains again,
        bounded by the RetryPolicy, so this always terminates."""
        uids = [self.submit(x) for x in np.asarray(xs)]
        results: Dict[int, Completed] = {}
        while len(self):
            for c in self.step():
                results[c.uid] = c
        return [results[u] for u in uids]

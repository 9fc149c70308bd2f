"""Online refinery: closed-loop hypersolver refinement from live traffic.

The paper trains the correction g once, offline, by fitting the local
truncation residual (Sec. 3.2, Eq. 6 — ``core/residual.py``); serving
then throws that exact signal away on every probe and solve. This module
closes the loop, in three pieces layered beside (never inside) the
serving loops:

1. **Residual ledger** (``ResidualLedger``) — both serving loops
   optionally capture per-segment ``(s, z, eps, dz, R)`` residual samples
   from the states their cells already materialize: the in-flight
   scheduler from interior healthy slot rows at retire time, the drain
   engine from probe states at admission. ``R`` is the Eq. 6 residual
   computed on-device against a finer reference step (two half-steps of
   RK4), so fitting later needs neither the vector field nor a
   trajectory. The buffer is a bounded, seeded reservoir (algorithm R)
   behind an explicit ``capture_rate`` gate; the hot path pays at most
   ONE extra readout per retire, the capture never mutates serving state
   and is never priced by the cost oracle — capture-enabled completions
   stay uid-for-uid bitwise identical to capture-disabled ones
   (pinned in tests/test_refinery.py, benched in bench_refinery.py).

2. **Background trainer** (``Refinery.train_tick``) — a cooperative step
   budget interleaved BETWEEN scheduler ticks (no threads touch the
   compiled path): sample a ledger batch, run the shared
   ``core/train.py::make_fit_step`` over
   ``core/residual.py::ledger_fitting_loss``, and checkpoint candidate
   params via ``checkpoint/manager.py`` async save.

3. **Shadow scorer + promotion gate** (``Refinery.maybe_promote``) —
   replay a held-out seeded request set through a SHADOW engine (its own
   pools; the live loops are never drained), scoring candidate-vs-current
   g on agreement against a fine frozen reference and on held-out
   residual norm. Promotion only on non-regression; a promoted candidate
   hot-swaps into the running engines/schedulers between segments
   (``hot_swap_g`` — params are traced cell INPUTS, so the swap compiles
   nothing), and ``check_promoted`` re-scores post-promotion and swaps
   the previous params back on regression.

The params-are-inputs invariant this rests on lives in
``Integrator.segment_cell(g_apply=)`` and the two loops' probe/solve
cells; ``docs/architecture.md`` ("the refinery layer") is the prose
twin.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.integrate import (
    Integrator, _bcast, rk_stages, tree_axpy, tree_lincomb,
)
from repro.core.residual import flow_fitting_loss, ledger_fitting_loss
from repro.core.tableaus import get as get_tableau
from repro.core.train import make_fit_step
from repro.optim import adamw
from repro.optim.schedules import cosine_annealing

__all__ = ["ResidualLedger", "Refinery", "RefineryConfig"]


# --------------------------------------------------------------- the ledger ----

class ResidualLedger:
    """Bounded, seeded-reservoir host buffer of serving-time residual
    samples, plus the jitted capture cells that produce them.

    One sample is ``(s, eps, z, dz, R)`` for a single request row: the
    state ``z`` at depth ``s``, its step size, the field eval
    ``dz = f(s, z)``, and the Eq. 6 local truncation residual
    ``R = [z_ref(s+eps) - z - eps*psi] / eps^{p+1}`` with ``z_ref`` a
    two-half-step RK4 reference — exactly the target the paper fits g
    to, measured on the traffic actually being served.

    Capture cost discipline: one jitted call per capture event, full
    batch/pool width (so the cell set is bounded: one per (shape, width)
    — callers pad to pow2 widths), gated by ``capture_rate`` on the
    ledger's own seeded RNG. Nothing here touches serving state, the
    serving RNG, or the cost oracle's clock.

    ``holdout_every``: every Nth kept sample is diverted to a held-out
    split the trainer never samples — the shadow scorer's residual-norm
    metric (``holdout_batch``)."""

    def __init__(self, model, capacity: int = 512,
                 capture_rate: float = 1.0, seed: int = 0,
                 holdout_every: int = 8):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if not (0.0 <= capture_rate <= 1.0):
            raise ValueError(
                f"capture_rate must be in [0, 1], got {capture_rate}")
        self.model = model
        self.capacity = int(capacity)
        self.capture_rate = float(capture_rate)
        self.holdout_every = int(holdout_every)
        self._rng = np.random.RandomState(seed)
        self._samples: List[Tuple] = []      # (s, eps, z, dz, R) rows
        self._holdout: List[Tuple] = []
        self.seen = 0                        # kept rows ever offered
        self.captures = 0                    # capture events that fired
        self._cells: Dict[Tuple, Any] = {}

    # ------------------------------------------------------------- state ----
    @property
    def fill(self) -> int:
        return len(self._samples)

    @property
    def holdout_fill(self) -> int:
        return len(self._holdout)

    # ------------------------------------------------------ capture cells ----
    def _cell(self, shape: Tuple[int, ...], dtype, width: int):
        """One jitted ``(params, xs, z, s, eps) -> (dz, R)`` capture cell per
        (request shape, row width): base-tableau stage evals + psi, a
        finer RK4 reference (two half steps), and the Eq. 6 residual —
        all row-wise, nothing mutated."""
        key = (tuple(shape), str(dtype), int(width))
        if key not in self._cells:
            m = self.model
            tab = m.integ.tableau
            ref_tab = get_tableau("rk4")
            p1 = tab.order + 1

            @jax.jit
            def cap(params, xs, z, s, eps):
                f = m.field_of(params, xs)
                stages = rk_stages(f, tab, s, eps, z)
                dz = stages[0]
                psi = tree_lincomb(tab.b, stages)

                def fine(s_, h_, z_):
                    st = rk_stages(f, ref_tab, s_, h_, z_)
                    return tree_axpy(h_, tree_lincomb(ref_tab.b, st), z_)

                h2 = eps * 0.5
                z_ref = fine(s + h2, h2, fine(s, h2, z))
                # R = [z_ref - z - eps*psi] / eps^{p+1}  (paper Eq. 6,
                # with the finer step standing in for the ground truth)
                R = jax.tree_util.tree_map(
                    lambda zr, zz, ps: (zr - zz - _bcast(eps, zz) * ps)
                    / _bcast(eps ** p1, zz),
                    z_ref, z, psi)
                return dz, R

            self._cells[key] = cap
        return self._cells[key]

    # ----------------------------------------------------------- capture ----
    def _fires(self) -> bool:
        if self.capture_rate <= 0.0:
            return False
        if self.capture_rate >= 1.0:
            return True
        return bool(self._rng.random_sample() < self.capture_rate)

    def _offer(self, sample: Tuple) -> None:
        """Reservoir-add one kept sample (algorithm R), diverting every
        ``holdout_every``-th to the held-out split (cyclic overwrite once
        that split is at capacity)."""
        self.seen += 1
        if self.holdout_every and self.seen % self.holdout_every == 0:
            if len(self._holdout) < self.capacity:
                self._holdout.append(sample)
            else:
                self._holdout[self.seen % self.capacity] = sample
            return
        if len(self._samples) < self.capacity:
            self._samples.append(sample)
        else:
            j = int(self._rng.randint(0, self.seen))
            if j < self.capacity:
                self._samples[j] = sample

    def capture(self, xs, z, s, eps, keep=None) -> int:
        """Capture residual rows from a materialized request batch (the
        drain engine's admission hook). ``xs`` is the (B, ...) input
        batch, ``z`` the matching state pytree, ``s``/``eps`` (B,) float
        rows; ``keep`` masks rows in (quarantine-bound rows out). Pads to
        a pow2 row width so the capture-cell set stays bounded. Returns
        the number of rows offered to the reservoir."""
        if not self._fires():
            return 0
        xs = jnp.asarray(xs)
        B = xs.shape[0]
        if B == 0:
            return 0
        w = 1 << max(B - 1, 0).bit_length()
        if w != B:
            pad = jnp.arange(w) % B
            xs = xs[pad]
            z = jax.tree_util.tree_map(lambda l: l[pad], z)
            s = np.asarray(s)[np.arange(w) % B]
            eps = np.asarray(eps)[np.arange(w) % B]
        mask = np.ones(B, bool) if keep is None else \
            np.asarray(keep, bool).copy()
        cell = self._cell(tuple(xs.shape[1:]), xs.dtype, w)
        dz, R = cell(self.model.params, xs, z, jnp.asarray(s, jnp.float32),
                     jnp.asarray(eps, jnp.float32))
        return self._ingest(np.asarray(s), np.asarray(eps), z, dz, R,
                            np.flatnonzero(mask))

    def capture_pool(self, pool, rows: np.ndarray) -> int:
        """Capture residual rows from an in-flight slot pool (the
        scheduler's retire hook): one full-pool-width jitted readout of
        ``(dz, R)`` at each live row's current ``s = s0 + k*eps``, then a
        host-side gather of just ``rows``. The pool's resident buffers
        are READ (gathers enqueued before the next donating launch),
        never written."""
        if len(rows) == 0 or not self._fires():
            return 0
        s0 = self.model.span[0]
        s = (s0 + pool.k.astype(np.float64)
             * pool.eps.astype(np.float64)).astype(np.float32)
        cell = self._cell(tuple(pool.shape), pool.xs.dtype,
                          int(pool.k.shape[0]))
        dz, R = cell(pool.sched.params, pool._xs_dev, pool.z,
                     jnp.asarray(s), jnp.asarray(pool.eps, jnp.float32))
        return self._ingest(s, pool.eps, pool.z, dz, R, rows)

    def _ingest(self, s, eps, z, dz, R, rows) -> int:
        """Materialize the captured rows, drop non-finite ones (a row can
        go non-finite inside the reference step before the quarantine
        layer sees it), and offer the rest to the reservoir."""
        self.captures += 1
        z_h = jax.tree_util.tree_map(np.asarray, z)
        dz_h = jax.tree_util.tree_map(np.asarray, dz)
        R_h = jax.tree_util.tree_map(np.asarray, R)
        offered = 0
        for i in rows:
            i = int(i)
            row = lambda t: jax.tree_util.tree_map(lambda l: l[i], t)
            Ri = row(R_h)
            if not all(np.isfinite(l).all()
                       for l in jax.tree_util.tree_leaves(Ri)):
                continue
            self._offer((np.float32(s[i]), np.float32(eps[i]),
                         row(z_h), row(dz_h), Ri))
            offered += 1
        return offered

    # ---------------------------------------------------------- batching ----
    @staticmethod
    def _stack(samples: Sequence[Tuple]) -> Dict[str, Any]:
        s = np.asarray([t[0] for t in samples], np.float32)
        eps = np.asarray([t[1] for t in samples], np.float32)
        stack = lambda col: jax.tree_util.tree_map(
            lambda *ls: np.stack(ls), *[t[col] for t in samples])
        return {"s": s, "eps": eps, "z": stack(2), "dz": stack(3),
                "R": stack(4)}

    def sample_batch(self, n: int, rng: np.random.RandomState
                     ) -> Dict[str, Any]:
        """Stacked training batch of ``n`` reservoir samples, drawn with
        replacement (so the batch width — and the fit-step compilation —
        is constant from the first usable fill onward)."""
        if not self._samples:
            raise ValueError("empty ledger: nothing captured yet")
        idx = rng.randint(0, len(self._samples), size=n)
        return self._stack([self._samples[i] for i in idx])

    def holdout_batch(self, n: int) -> Optional[Dict[str, Any]]:
        """Deterministic fixed-width batch from the held-out split (rows
        cycled to width ``n`` so the eval cell compiles once); None until
        anything is held out."""
        if not self._holdout:
            return None
        return self._stack([self._holdout[i % len(self._holdout)]
                            for i in range(n)])

    # ------------------------------------------------------------- flush ----
    def flush(self, path: str) -> int:
        """Persist the ledger (train + holdout splits) as an .npz — the
        graceful-drain hook (serve.py SIGTERM/SIGINT): captured residuals
        survive the pre-emption for the next refinery run. Returns the
        number of rows written."""
        rows = self._samples + self._holdout
        if not rows:
            np.savez(path, s=np.zeros((0,), np.float32),
                     eps=np.zeros((0,), np.float32), n_train=0)
            return 0
        cols = self._stack(rows)
        flat = {"s": cols["s"], "eps": cols["eps"],
                "n_train": len(self._samples)}
        for name in ("z", "dz", "R"):
            for i, leaf in enumerate(
                    jax.tree_util.tree_leaves(cols[name])):
                flat[f"{name}_{i}"] = leaf
        np.savez(path, **flat)
        return len(rows)


# -------------------------------------------------------------- the trainer ----

@dataclasses.dataclass(frozen=True)
class RefineryConfig:
    """Knobs for the cooperative background trainer + promotion gate."""

    steps_per_tick: int = 2       # fit steps per scheduler tick
    batch_size: int = 32          # ledger rows per fit step
    min_fill: int = 32            # ledger fill before training starts
    lr: float = 3e-3              # AdamW peak lr (cosine to lr_min)
    lr_min: float = 1e-4
    weight_decay: float = 1e-6
    grad_clip: float = 10.0
    total_steps: int = 1000       # cosine horizon for the candidate
    ckpt_every: int = 50          # candidate steps between async saves
    shadow_every: int = 100       # candidate steps between shadow scores
    agreement_margin: float = 0.0  # allowed agreement slack at the gate
    resid_margin: float = 0.0     # allowed residual-norm slack at the gate
    holdout_rows: int = 64        # fixed eval width over the holdout split
    ref_K: int = 64               # fine frozen-reference mesh length
    seed: int = 0


class Refinery:
    """The closed loop: ledger batches -> candidate g -> shadow score ->
    promotion gate -> hot-swap (with rollback). Cooperative by
    construction — every method runs on the caller's thread between
    scheduler ticks; only the checkpoint write rides the
    CheckpointManager's async saver thread, which never touches jax.

    ``model`` must be parametric (``g_apply``/``g_params``);
    ``shadow_xs`` is the held-out seeded request set replayed by the
    shadow scorer (reuse launch/workload.py generators with a reserved
    seed). ``targets`` passed to ``tick``/``maybe_promote`` are live
    ``MultiRateEngine``/``InflightScheduler`` instances — promotion
    hot-swaps them between segments; their slot pools are never drained.
    """

    def __init__(self, model, ledger: ResidualLedger,
                 cfg: Optional[RefineryConfig] = None, *,
                 ecfg=None, shadow_xs=None, ckpt_dir: Optional[str] = None,
                 score_fn: Optional[Callable] = None,
                 param_site: str = "g"):
        from repro.launch.engine import EngineConfig, MultiRateEngine
        if param_site not in ("g", "flow"):
            raise ValueError(
                f"param_site={param_site!r}: expected 'g' (refine the "
                "hypersolver correction) or 'flow' (refine the K=0 flow "
                "head, core/flowhead.py)")
        if param_site == "g" and model.g_apply is None:
            raise ValueError(
                "Refinery needs a parametric model (DepthModel.g_apply/"
                "g_params): a closure g cannot hot-swap without retraces")
        if param_site == "flow" and model.flow_apply is None:
            raise ValueError(
                "Refinery(param_site='flow') needs a model with a flow "
                "head (DepthModel.flow_apply/flow_params)")
        self.model = model
        self.ledger = ledger
        self.cfg = cfg or RefineryConfig()
        self.param_site = param_site
        self._rng = np.random.RandomState(self.cfg.seed)

        # candidate/current params: current is what serving runs; the
        # candidate trains ahead of it on ledger batches
        as_dev = lambda t: jax.tree_util.tree_map(jnp.asarray, t)
        self.current = as_dev(model.g_params if param_site == "g"
                              else model.flow_params)
        self.candidate = self.current
        self.steps = 0                      # candidate fit steps taken
        self.last_loss: Optional[float] = None
        self.last_promotion: Optional[int] = None
        self.last_verdict: Optional[Dict] = None
        self.promotions = 0
        self.rejections = 0
        self.rollbacks = 0
        self._prev: Optional[Tuple[Any, Dict]] = None   # rollback handle
        self._current_score: Optional[Dict] = None

        opt = adamw(
            cosine_annealing(self.cfg.lr, self.cfg.lr_min,
                             self.cfg.total_steps),
            weight_decay=self.cfg.weight_decay)
        self._opt_state = opt.init(self.candidate)

        if param_site == "g":
            ga = model.g_apply

            def loss_fn(gp, s, eps, z, dz, R):
                g = lambda e, s_, z_, dz_: ga(gp, e, s_, z_, dz_)
                return ledger_fitting_loss(g, s, eps, z, dz, R)
        else:
            # the flow head fits the SAME ledger rows: for a structured
            # F = z + eps*dz + eps^{p+1}*net, flow_fitting_loss reduces
            # exactly to ledger_fitting_loss on the inner net — one
            # residual stream trains both tiers. relative=True because
            # the router only hands the flow tier confidently-easy rows:
            # the live ledger is difficulty-mixed, and the raw objective
            # would trade easy-row accuracy for hard-row magnitudes
            fa = model.flow_apply
            order = model.integ.order

            def loss_fn(fp, s, eps, z, dz, R):
                flow = lambda e, s_, z_, dz_: fa(fp, e, s_, z_, dz_)
                return flow_fitting_loss(flow, s, eps, z, dz, R,
                                         order=order, relative=True)

        self._fit_step = make_fit_step(loss_fn, opt, self.cfg.grad_clip)
        self._eval_loss = jax.jit(loss_fn)

        # shadow scorer: its OWN engine instance over the same model and
        # policy — candidate params score on cells that take gp as a
        # traced input, so scoring N candidates compiles once. At
        # param_site="flow" the engine is replaced by a dedicated
        # full-span flow cell (the K=0 tier has no mesh to serve).
        self._shadow_xs = None if shadow_xs is None else np.asarray(
            shadow_xs)
        self._score_fn = score_fn or self._argmax_agreement
        self._shadow_engine = None
        self._flow_score_fn = None
        self._ref_out = None
        if self._shadow_xs is not None:
            if param_site == "g":
                self._shadow_engine = MultiRateEngine(
                    model, ecfg or EngineConfig())
            else:
                self._flow_score_fn = self._flow_cell()
            self._ref_out = np.asarray(self._reference_cell()(
                model.params, jnp.asarray(self._shadow_xs)))

        self._ckpt = None
        if ckpt_dir is not None:
            from repro.checkpoint import CheckpointManager
            self._ckpt = CheckpointManager(ckpt_dir, keep=3,
                                           async_save=True)

    # ---------------------------------------------------------- training ----
    def train_tick(self) -> Optional[float]:
        """One cooperative training slice: up to ``steps_per_tick`` fit
        steps over ledger batches (no-op below ``min_fill``), candidate
        checkpointed asynchronously every ``ckpt_every`` steps. Returns
        the last batch loss, or None if the ledger is not ready."""
        if self.ledger.fill < max(self.cfg.min_fill, 1):
            return None
        loss = None
        for _ in range(self.cfg.steps_per_tick):
            b = self.ledger.sample_batch(self.cfg.batch_size, self._rng)
            self.candidate, self._opt_state, l = self._fit_step(
                self.candidate, self._opt_state, self.steps,
                b["s"], b["eps"], b["z"], b["dz"], b["R"])
            self.steps += 1
            loss = float(l)
            if self._ckpt is not None \
                    and self.steps % self.cfg.ckpt_every == 0:
                self._ckpt.save(self.steps, self.candidate)
        self.last_loss = loss
        return loss

    # ----------------------------------------------------------- scoring ----
    def _reference_cell(self):
        """Fine frozen reference for shadow agreement: the BASE tableau
        (no correction) at ``ref_K`` steps — the same ground-truth proxy
        the offline benches use."""
        m = self.model
        K = int(self.cfg.ref_K)
        ref = Integrator(tableau=m.integ.tableau)

        @jax.jit
        def run(params, xs):
            z0 = m.embed(params, xs)
            Ks = jnp.full((xs.shape[0],), K, jnp.int32)
            zT = ref.solve_multirate(m.field_of(params, xs), z0, m.span,
                                     Ks, K)
            return m.readout(params, xs, zT)

        return run

    def _flow_cell(self):
        """Shadow scorer for ``param_site="flow"``: the candidate flow
        params serve the held-out set as the K=0 tier would — one
        full-span F eval off ``(z0, dz0)`` plus readout — and score
        agreement against the same fine frozen reference. Params ride as
        a traced input, so scoring N candidates compiles once."""
        m = self.model
        h, s0 = m.span[1] - m.span[0], m.span[0]
        fa = m.flow_apply

        @jax.jit
        def run(params, xs, fp):
            z0 = m.embed(params, xs)
            dz0 = m.field_of(params, xs)(s0, z0)
            return m.readout(params, xs, fa(fp, h, s0, z0, dz0))

        return run

    @staticmethod
    def _argmax_agreement(outs: np.ndarray, ref: np.ndarray) -> float:
        """Default agreement: fraction of matching argmax over the last
        output axis (classification-style readouts; pass ``score_fn``
        for anything else)."""
        return float((np.argmax(outs, -1) == np.argmax(ref, -1)).mean())

    def shadow_score(self, gp) -> Dict[str, float]:
        """Score params on the held-out trace: agreement vs the fine
        frozen reference, mean NFE, and held-out residual-norm loss.
        Runs on the refinery's own shadow engine — live pools untouched."""
        out: Dict[str, float] = {}
        if self._shadow_engine is not None:
            self._shadow_engine.hot_swap_g(gp)
            recs = self._shadow_engine.run(self._shadow_xs)
            recs = sorted(recs, key=lambda c: c.uid)
            outs = np.stack([c.outputs for c in recs])
            out["agreement"] = self._score_fn(outs, self._ref_out)
            out["mean_nfe"] = float(np.mean([c.nfe for c in recs]))
        elif self._flow_score_fn is not None:
            outs = np.asarray(self._flow_score_fn(
                self.model.params, jnp.asarray(self._shadow_xs), gp))
            out["agreement"] = self._score_fn(outs, self._ref_out)
        hb = self.ledger.holdout_batch(self.cfg.holdout_rows)
        if hb is not None:
            out["resid"] = float(self._eval_loss(
                gp, hb["s"], hb["eps"], hb["z"], hb["dz"], hb["R"]))
        return out

    def _non_regression(self, cand: Dict, cur: Dict) -> bool:
        """The promotion gate: candidate must not regress on any metric
        both scores carry (agreement within ``agreement_margin``,
        held-out residual within ``resid_margin``)."""
        ok = True
        if "agreement" in cand and "agreement" in cur:
            ok &= cand["agreement"] >= cur["agreement"] \
                - self.cfg.agreement_margin
        if "resid" in cand and "resid" in cur:
            ok &= cand["resid"] <= cur["resid"] + self.cfg.resid_margin
        return bool(ok)

    def _swap(self, target, params) -> None:
        """Hot-swap ``params`` into a live serving loop at this
        refinery's param site — ``hot_swap_g`` or ``hot_swap_flow``,
        both zero-retrace by the params-are-inputs invariant."""
        if self.param_site == "g":
            target.hot_swap_g(params)
        else:
            target.hot_swap_flow(params)

    # ---------------------------------------------------- promote / roll ----
    def maybe_promote(self, targets: Sequence = ()) -> Dict:
        """Shadow-score the candidate against the serving params and
        hot-swap it into every target ONLY on non-regression. The
        rejected candidate keeps training — nothing it computed is ever
        observable in serving outputs. Returns the verdict dict
        (bench_refinery.py records these).

        Both sides are scored FRESH each gate: the held-out residual
        split keeps growing between gates, and judging the candidate on
        today's holdout against a current score cached on yesterday's
        would bias the comparison either way."""
        cand = self.shadow_score(self.candidate)
        cur = self.shadow_score(self.current)
        self._current_score = cur
        promoted = self._non_regression(cand, cur)
        self.last_verdict = {
            "step": self.steps, "promoted": promoted,
            "candidate": cand, "current": cur,
        }
        if promoted:
            self._prev = (self.current, cur)
            self.current = self.candidate
            self._current_score = cand
            for t in targets:
                self._swap(t, self.current)
            self.promotions += 1
            self.last_promotion = self.steps
        else:
            self.rejections += 1
        return self.last_verdict

    def check_promoted(self, targets: Sequence = ()) -> Optional[bool]:
        """Post-promotion guard: re-score the PROMOTED params (the
        held-out residual split keeps growing, so the score can drift
        after promotion) and roll the previous params back into every
        target if they now regress below the pre-promotion params.
        BOTH sides re-score on today's holdout — comparing a fresh
        promoted score against the stale pre-promotion baseline would
        fire rollbacks on holdout growth alone. None if there is
        nothing to check, else whether a rollback fired."""
        if self._prev is None:
            return None
        score = self.shadow_score(self.current)
        prev_params, _ = self._prev
        prev_score = self.shadow_score(prev_params)
        if self._non_regression(score, prev_score):
            self._current_score = score
            return False
        for t in targets:
            self._swap(t, prev_params)
        self.current = prev_params
        self._current_score = prev_score
        self._prev = None
        self.rollbacks += 1
        return True

    # -------------------------------------------------------- tick / misc ----
    def tick(self, targets: Sequence = ()) -> None:
        """The between-scheduler-ticks slice serve.py drives: train a
        little, and every ``shadow_every`` candidate steps run the
        shadow gate (then the post-promotion guard on the next gate)."""
        before = self.steps
        self.train_tick()
        crossed = (self.steps // self.cfg.shadow_every
                   > before // self.cfg.shadow_every)
        if crossed and self.steps > 0:
            self.check_promoted(targets)
            self.maybe_promote(targets)

    def flush(self) -> None:
        """Block until any pending async candidate checkpoint is on disk
        (the graceful-drain hook; ledger flushing is the caller's call —
        it needs a path)."""
        if self._ckpt is not None:
            self._ckpt.wait()

    def status(self) -> Dict[str, Any]:
        """One-line state for the serve.py live progress line."""
        return {
            "ledger_fill": self.ledger.fill,
            "ledger_seen": self.ledger.seen,
            "candidate_step": self.steps,
            "last_loss": self.last_loss,
            "last_promotion": self.last_promotion,
            "promotions": self.promotions,
            "rejections": self.rejections,
            "rollbacks": self.rollbacks,
        }

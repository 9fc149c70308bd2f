"""Serving CLI — batching/eps policy lives in ``launch/engine.py``; this
module only parses flags and reports.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_4b --reduced \
        --batch 4 --prompt-len 16 --gen 32 [--solver hyper_euler --nfe 4] \
        [--g-ckpt /path/to/g --g-rank 32] [--multirate --tol 1e-2 \
         --buckets 2,4,8]

solver=discrete (default): standard full-depth cached decode.
solver=euler|heun|...|hyper_* : continuous-depth scoring. Fixed-K serving
with --nfe K, or error-controlled multi-rate serving with --multirate: a
cheap per-request probe assigns each request a mesh-length bucket and
same-shape requests pack into mixed-K batches solved in one masked
multi-rate pass — with --fused the whole per-step update (per-sample eps,
correction, freeze mask) is a single runtime-eps Pallas kernel pass, for
every bucket mix (see launch/engine.py). ``hyper_*`` solvers apply a
trained hypersolver correction loaded via --g-ckpt (HyperEuler etc.).
Reports per-request NFE and argmax agreement vs the full-depth forward.
--flow-ckpt + --flow-threshold add the K=0 flow tier on top of
--multirate: requests whose probe error sits below threshold*tol are
served by a learned solution operator in ONE net eval (core/flowhead.py);
non-finite flow evals escalate back into the K-bucket ladder
(status="escalated").

--inflight swaps the drain-the-queue engine for the continuous-batching
slot-pool scheduler (launch/scheduler.py): --slots slots advance --seg
depth steps per scheduling round, finished requests retire and refill
between segments. --arrival-trace poisson|bursty replays a seeded
streaming arrival trace (--arrival-rate requests per cost unit) through
the scheduler and reports p50/p99 latency + queue wait + masked-step
waste (launch/workload.py); ``none`` submits the whole batch at once.
--mesh N shards the slot pool over N devices ('data' axis, --slots
global rows split row-wise; launch/mesh.py::make_serving_mesh) — one
admission queue, per-device sub-pools, no collectives. --overlap swaps
the synchronous tick for the pipelined one (host bookkeeping overlaps
the in-flight device segment; uid-for-uid identical completions), and
--profile-dir saves a jax.profiler trace of the serving loop.

Request hardening (--inflight only; docs/serving.md "Failure
semantics"): --deadline gives every request that much oracle-clock
slack before it is dropped/evicted status="deadline"; --queue-cap
bounds the admission queue, with --overload-policy picking what an
over-cap submit does (shed terminally / degrade one bucket coarser /
block the caller). Diverged solves are quarantined on device and
retried once at a finer bucket before returning best-effort.

--refine attaches the online refinery (launch/refinery.py): serving
captures per-segment residual samples into a bounded ledger
(--capture-rate, --ledger-cap), a background trainer fits a candidate
correction between scheduler ticks (--refine-steps per tick,
checkpointed to --refine-dir), and every --shadow-every candidate steps
a shadow scorer replays a held-out trace and hot-swaps the candidate in
ONLY on non-regression — no retrace, no pool drain. --progress-every N
prints a live line every N ticks (hardening counters + refinery state).
SIGTERM/SIGINT drain gracefully: admission stops, in-flight slots flush,
the ledger (--ledger-out) and any pending candidate checkpoint land on
disk before exit.

Full flag reference with worked examples: docs/serving.md.
"""
from __future__ import annotations

import argparse
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get
from repro.launch.compile_cache import use_compile_cache
from repro.launch.engine import (
    EngineConfig, MultiRateEngine, greedy_generate, lm_depth_model,
    load_flow_params, load_g_params,
)
from repro.models.lm import discrete_nfe, group_layout, init_lm, lm_forward


def _profiled(profile_dir):
    """``jax.profiler.trace`` around the serving loop when --profile-dir
    is set (a no-op context otherwise): the saved timeline shows host
    phases against device segments, which is how overlap regressions are
    diagnosed (docs/serving.md)."""
    import contextlib
    if not profile_dir:
        return contextlib.nullcontext()
    return jax.profiler.trace(profile_dir)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--solver", default="discrete")
    ap.add_argument("--nfe", type=int, default=0,
                    help="fixed mesh length K (ignored with --multirate)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--g-ckpt", default=None,
                    help="CheckpointManager dir of a trained LM hypersolver "
                         "correction (enables hyper_* solvers)")
    ap.add_argument("--g-rank", type=int, default=32,
                    help="rank of the g_omega checkpoint being restored")
    ap.add_argument("--flow-ckpt", default=None,
                    help="CheckpointManager dir of a trained K=0 flow head "
                         "(core/flowhead.py); requires --flow-threshold")
    ap.add_argument("--flow-rank", type=int, default=64,
                    help="rank of the flow-head checkpoint being restored")
    ap.add_argument("--flow-threshold", type=float, default=0.0,
                    help="route requests whose probe error is below this "
                         "fraction of --tol to the K=0 flow tier (one net "
                         "eval, no solver; --multirate only). 0 disables "
                         "the tier; flow evals that come back non-finite "
                         "escalate into the K-bucket ladder "
                         "(status='escalated')")
    ap.add_argument("--multirate", action="store_true",
                    help="error-controlled per-request step sizes "
                         "(launch/engine.py) instead of one fixed K")
    ap.add_argument("--tol", type=float, default=1e-2,
                    help="probe local-error tolerance for --multirate")
    ap.add_argument("--buckets", default="2,4,8",
                    help="comma-separated serving K buckets for --multirate")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--fused", action="store_true",
                    help="route batch solves through the runtime-eps "
                         "Pallas kernel (any bucket mix fuses)")
    ap.add_argument("--inflight", action="store_true",
                    help="serve through the in-flight slot-pool scheduler "
                         "(launch/scheduler.py) instead of the drain engine")
    ap.add_argument("--seg", type=int, default=2,
                    help="depth steps per scheduling segment (--inflight)")
    ap.add_argument("--slots", type=int, default=4,
                    help="slot-pool width per request shape (--inflight)")
    ap.add_argument("--arrival-trace", default="none",
                    choices=["none", "poisson", "bursty"],
                    help="replay a seeded streaming arrival trace through "
                         "the scheduler (--inflight only)")
    ap.add_argument("--arrival-rate", type=float, default=0.25,
                    help="poisson arrival rate / bursty burst pacing, in "
                         "requests per virtual cost unit")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the slot pool over N devices (--inflight "
                         "only): --slots is the GLOBAL pool width and must "
                         "be a multiple of N; on CPU force virtual devices "
                         "with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N")
    ap.add_argument("--cost-oracle", default="sequential",
                    choices=["sequential", "roofline"],
                    help="virtual-clock pricing (launch/oracle.py): "
                         "'sequential' counts sequential field evals "
                         "(batch-width free, the BENCH baseline unit); "
                         "'roofline' prices probes/segments/solves of the "
                         "served --arch in predicted device-us via the "
                         "analytic roofline model (roofline/costmodel.py)")
    ap.add_argument("--overlap", action="store_true",
                    help="pipelined in-flight loop (--inflight only): "
                         "dispatch segment N+1 while segment N's retire "
                         "metadata is still in flight (JAX async dispatch "
                         "+ donated carries); completions are uid-for-uid "
                         "identical to the synchronous loop")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request deadline SLACK on the oracle clock "
                         "(--inflight only): a request not finished "
                         "within this many cost units of its arrival is "
                         "dropped/evicted with status='deadline'; 0 = "
                         "no deadlines")
    ap.add_argument("--queue-cap", type=int, default=0,
                    help="bound the admission queue at this many waiting "
                         "requests (--inflight only); 0 = unbounded")
    ap.add_argument("--overload-policy", default="shed",
                    choices=["shed", "degrade", "block"],
                    help="what an over-cap submit does (--queue-cap): "
                         "'shed' refuses terminally (status='shed'), "
                         "'degrade' admits one K-bucket coarser under "
                         "pressure, 'block' raises to the caller")
    ap.add_argument("--profile-dir", default=None,
                    help="wrap the serving loop in jax.profiler.trace and "
                         "save the trace here (inspect with TensorBoard/"
                         "Perfetto) — wall-clock regressions become "
                         "diagnosable from the saved timeline")
    ap.add_argument("--refine", action="store_true",
                    help="attach the online refinery (--inflight only): "
                         "capture serving-time residuals into a ledger, "
                         "fit a candidate correction between scheduler "
                         "ticks, shadow-score it on a held-out trace and "
                         "hot-swap it in only on non-regression "
                         "(launch/refinery.py)")
    ap.add_argument("--refine-dir", default=None,
                    help="CheckpointManager directory for async candidate "
                         "checkpoints (--refine); restorable via --g-ckpt "
                         "on a later run")
    ap.add_argument("--capture-rate", type=float, default=1.0,
                    help="fraction of capture events the residual ledger "
                         "keeps (--refine); 0 disables capture entirely")
    ap.add_argument("--ledger-cap", type=int, default=512,
                    help="residual-ledger reservoir capacity in samples "
                         "(--refine)")
    ap.add_argument("--refine-steps", type=int, default=2,
                    help="candidate fit steps per scheduler tick "
                         "(--refine): the cooperative training budget "
                         "interleaved between segments")
    ap.add_argument("--shadow-every", type=int, default=50,
                    help="candidate steps between shadow-gate evaluations "
                         "(--refine)")
    ap.add_argument("--ledger-out", default=None,
                    help="flush the residual ledger to this .npz on exit "
                         "or graceful drain (--refine)")
    ap.add_argument("--progress-every", type=int, default=0,
                    help="print a live progress line every N scheduler "
                         "ticks (--inflight): hardening counters "
                         "(quarantined/deadline/requeued/shed) plus "
                         "refinery state under --refine; 0 = off")
    args = ap.parse_args()
    if args.mesh and not args.inflight:
        # same policy as --g-ckpt: a silently ignored flag would let a
        # run labeled multi-device report single-device numbers
        raise SystemExit("--mesh shards the in-flight slot pool; pass "
                         "--inflight with it (the drain engine has no "
                         "slot pool to shard)")
    if args.overlap and not args.inflight:
        # same policy: a run labeled overlapped must not silently report
        # drain-engine numbers
        raise SystemExit("--overlap pipelines the in-flight segment loop; "
                         "pass --inflight with it (the drain engine has "
                         "no segment loop to overlap)")
    if (args.deadline or args.queue_cap) and not args.inflight:
        # same policy: a run labeled deadline-bounded or overload-capped
        # must not silently report unbounded drain numbers
        raise SystemExit("--deadline/--queue-cap harden the in-flight "
                         "scheduler's admission; pass --inflight with "
                         "them")
    if args.overload_policy != "shed" and not args.queue_cap:
        raise SystemExit(f"--overload-policy {args.overload_policy} is "
                         "meaningless without --queue-cap (an unbounded "
                         "queue never overloads)")
    if args.refine and not args.inflight:
        # same policy as --mesh/--overlap: the refinery trains BETWEEN
        # scheduler ticks; the drain engine has no tick to interleave
        raise SystemExit("--refine interleaves with the in-flight "
                         "scheduler's ticks; pass --inflight with it")
    if args.refine and args.solver == "discrete":
        raise SystemExit("--refine fits a hypersolver correction; pass a "
                         "continuous --solver (e.g. euler/hyper_euler)")
    if not args.refine and (
            args.refine_dir or args.ledger_out
            or args.capture_rate != 1.0 or args.ledger_cap != 512
            or args.refine_steps != 2 or args.shadow_every != 50):
        raise SystemExit("--refine-dir/--capture-rate/--ledger-cap/"
                         "--refine-steps/--shadow-every/--ledger-out "
                         "tune the online refinery; pass --refine with "
                         "them (a silently ignored knob would mislabel "
                         "the run)")
    if args.progress_every and not args.inflight:
        raise SystemExit("--progress-every reports the in-flight "
                         "scheduler's tick counters; pass --inflight "
                         "with it")
    if args.flow_threshold and not args.multirate:
        # the flow tier routes off the admission probe's difficulty
        # estimate; fixed-K serving never probes
        raise SystemExit("--flow-threshold routes off the multi-rate "
                         "admission probe; pass --multirate with it "
                         "(fixed-K serving has no probe to route from)")
    if args.flow_threshold and not args.flow_ckpt:
        raise SystemExit("--flow-threshold needs --flow-ckpt (a trained "
                         "flow head): a fresh zero-init head is exactly "
                         "one full-span Euler step, which would mislabel "
                         "the K=0 tier's numbers")
    if args.flow_ckpt and not args.flow_threshold:
        # same policy as --g-ckpt/--mesh: a silently ignored checkpoint
        # would let a run labeled flow-tiered report ladder-only numbers
        raise SystemExit("--flow-ckpt is only read by the flow tier; "
                         "pass --flow-threshold > 0 with it")

    use_compile_cache()
    cfg = get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = jax.jit(init_lm, static_argnums=1)(
        jax.random.PRNGKey(args.seed), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0, cfg.vocab)

    if args.solver == "discrete":
        t0 = time.time()
        with _profiled(args.profile_dir):
            toks = greedy_generate(params, cfg, prompt, args.gen)
        dt = time.time() - t0
        print(f"[discrete] {args.batch}x{args.gen} tokens in {dt:.2f}s "
              f"({args.batch * args.gen / dt:.1f} tok/s), "
              f"NFE/token = {discrete_nfe(cfg)} groups")
        print("sample:", np.asarray(toks[0, :16]))
        return

    # continuous-depth scoring comparison at reduced NFE
    _, n_groups, _ = group_layout(cfg)
    g_params = None
    if args.g_ckpt:
        g_params = load_g_params(args.g_ckpt, cfg, rank=args.g_rank)
    if args.solver.startswith("hyper_") and g_params is None \
            and not args.refine:
        raise SystemExit(f"--solver {args.solver} needs --g-ckpt "
                         "(a trained correction checkpoint) — or "
                         "--refine to fit one from live traffic, "
                         "starting at a zero correction")

    flow_params = None
    if args.flow_ckpt:
        flow_params = load_flow_params(args.flow_ckpt, cfg,
                                       rank=args.flow_rank)

    buckets = tuple(int(b) for b in args.buckets.split(","))
    K_fixed = args.nfe or max(1, n_groups // 2)
    ecfg = EngineConfig(
        buckets=buckets if args.multirate else (K_fixed,),
        tol=args.tol,
        max_batch=args.max_batch,
        solver=args.solver,
        controller="auto" if args.multirate else "fixed",
        fixed_K=K_fixed,
        fused=args.fused,
        flow_threshold=args.flow_threshold,
    )
    model = lm_depth_model(params, cfg, solver=args.solver,
                           g_params=g_params, fused=args.fused,
                           refinable=args.refine, rank=args.g_rank,
                           flow_params=flow_params)
    # the roofline clock prices the SERVED arch at the prompt's context;
    # reported latency/wait switch to its unit (device-us) with it
    from repro.launch.oracle import make_oracle
    oracle = make_oracle(args.cost_oracle, cfg, ctx=args.prompt_len)

    full, _ = lm_forward(params, cfg, prompt)
    full_top = np.asarray(jnp.argmax(full, -1))

    if args.inflight:
        from repro.launch.scheduler import InflightScheduler
        from repro.launch.workload import (
            Arrival, bursty_trace, latency_stats, poisson_trace,
            replay_scheduler,
        )

        if args.arrival_trace != "none" and args.arrival_rate <= 0:
            raise SystemExit("--arrival-rate must be > 0 for "
                             f"--arrival-trace {args.arrival_trace}")
        mesh = None
        if args.mesh:
            from repro.launch.mesh import make_serving_mesh
            mesh = make_serving_mesh(args.mesh)

        ledger = refinery = None
        if args.refine:
            from repro.launch.refinery import (
                Refinery, RefineryConfig, ResidualLedger,
            )
            ledger = ResidualLedger(model, capacity=args.ledger_cap,
                                    capture_rate=args.capture_rate,
                                    seed=args.seed)
        sched = InflightScheduler(model, ecfg, slots=args.slots,
                                  seg=args.seg, mesh=mesh, oracle=oracle,
                                  overlap=args.overlap,
                                  deadline=args.deadline or None,
                                  queue_cap=args.queue_cap or None,
                                  overload_policy=args.overload_policy,
                                  ledger=ledger)
        if args.refine:
            # held-out seeded prompts the live trace never serves: the
            # shadow gate's replay set
            shadow = np.asarray(jax.random.randint(
                jax.random.PRNGKey(args.seed + 1000),
                (max(2, min(args.max_batch, 4)), args.prompt_len),
                0, cfg.vocab))
            refinery = Refinery(
                model, ledger,
                RefineryConfig(steps_per_tick=args.refine_steps,
                               shadow_every=args.shadow_every,
                               min_fill=min(32, args.ledger_cap),
                               ref_K=max(n_groups, max(buckets)),
                               seed=args.seed),
                ecfg=ecfg, shadow_xs=shadow, ckpt_dir=args.refine_dir)

        # graceful drain: first SIGTERM/SIGINT stops admission and lets
        # the in-flight slots flush; the ledger + any pending candidate
        # checkpoint land on disk below before the process exits
        draining = [False]

        def _on_signal(signum, frame):
            if draining[0]:
                raise KeyboardInterrupt  # second signal: give up the drain
            draining[0] = True
            print(f"[serve] caught signal {signum}: admission stopped, "
                  "draining in-flight slots")

        for sig in (signal.SIGTERM, signal.SIGINT):
            signal.signal(sig, _on_signal)

        ticks = [0]

        def on_tick(s):
            ticks[0] += 1
            if refinery is not None:
                refinery.tick([s])
            if args.progress_every \
                    and ticks[0] % args.progress_every == 0:
                parts = [f"t={s.now:.1f}", f"ticks={s.ticks}",
                         f"inflight={len(s)}",
                         f"quarantined={s.total_quarantined}",
                         f"deadline_evicted={s.total_deadline_evicted}",
                         f"requeued={s.total_requeued}",
                         f"shed={s.total_shed}"]
                if args.flow_threshold:
                    parts += [f"flow={s.total_flow_served}",
                              f"escalated={s.total_escalated}"]
                if refinery is not None:
                    st = refinery.status()
                    parts += [
                        f"ledger={st['ledger_fill']}/{ledger.capacity}",
                        f"cand_step={st['candidate_step']}",
                        f"promotions={st['promotions']}",
                        f"last_promotion={st['last_promotion']}"]
                print("[progress] " + " ".join(parts), flush=True)

        xs = np.asarray(prompt)
        t0 = time.time()
        with _profiled(args.profile_dir):
            if args.arrival_trace == "none" and refinery is None \
                    and not args.progress_every:
                results = sched.run(xs)
            else:
                if args.arrival_trace == "none":
                    # batch submit, replayed tick-by-tick so on_tick
                    # (refinery slice + progress line) still runs
                    trace = [Arrival(t=0.0, x=row) for row in xs]
                else:
                    trace = poisson_trace(xs, rate=args.arrival_rate,
                                          seed=args.seed) \
                        if args.arrival_trace == "poisson" else \
                        bursty_trace(xs, burst=args.slots,
                                     gap=args.slots / args.arrival_rate,
                                     seed=args.seed)
                report = replay_scheduler(
                    sched, trace, on_tick=on_tick,
                    should_admit=lambda: not draining[0])
                # records join back to prompt rows by uid (arrival order)
                results = sorted(report.records, key=lambda r: r.uid)
                if args.arrival_trace != "none":
                    print(f"[inflight {args.arrival_trace}] "
                          f"{latency_stats(report)}")
        dt = time.time() - t0
        if draining[0]:
            print(f"[serve] drained: {len(results)} completions flushed, "
                  f"{len(xs) - len(results)} arrivals never admitted")
        if refinery is not None:
            refinery.flush()   # pending async candidate checkpoint
            print(f"[refinery] {refinery.status()}")
        if ledger is not None and args.ledger_out:
            n_rows = ledger.flush(args.ledger_out)
            print(f"[ledger] flushed {n_rows} residual rows -> "
                  f"{args.ledger_out}")
        # shed/expired requests carry no outputs — agreement is over the
        # requests actually served (their status says why the rest
        # are not)
        # uid is submission order = prompt-row order, which survives a
        # partial (drained) run where enumerate order would not
        agree = {r.uid: float(np.mean(np.argmax(r.outputs, -1)
                                      == full_top[r.uid - 1]))
                 for r in results if r.outputs is not None}
        nfes = [r.nfe for r in results if r.outputs is not None]
        mode = "multirate" if args.multirate else f"K={K_fixed}"
        print(f"[{args.solver} {mode} inflight slots={args.slots} "
              f"seg={args.seg}] scored {len(agree)}/{args.batch} of "
              f"{args.batch}x{args.prompt_len} in "
              f"{dt:.2f}s; mean NFE {np.mean(nfes):.2f}/{n_groups} "
              f"(probe {sched.probe_nfe}); mean argmax agreement vs full "
              f"depth: {np.mean(list(agree.values())):.3f}")
        for r in results:
            # both record types (InflightCompleted / RequestRecord) stamp
            # queue_wait, latency, and status
            a = f"{agree[r.uid]:.3f}" if r.uid in agree else "-"
            print(f"  req {r.uid}: K={r.K} nfe={r.nfe} agree={a} "
                  f"wait={r.queue_wait:.1f} lat={r.latency:.1f} "
                  f"status={r.status}")
        return

    engine = MultiRateEngine(model, ecfg, oracle=oracle)
    t0 = time.time()
    with _profiled(args.profile_dir):
        results = engine.run(np.asarray(prompt))
    dt = time.time() - t0
    agree = [float(np.mean(np.argmax(r.outputs, -1) == full_top[i]))
             for i, r in enumerate(results)]
    nfes = [r.nfe for r in results]
    mode = "multirate" if args.multirate else f"K={K_fixed}"
    print(f"[{args.solver} {mode}] scored {args.batch}x{args.prompt_len} "
          f"in {dt:.2f}s; mean NFE {np.mean(nfes):.2f}/{n_groups} "
          f"(probe {engine.probe_nfe}); mean argmax agreement vs full "
          f"depth: {np.mean(agree):.3f}")
    for r, a in zip(results, agree):
        print(f"  req {r.uid}: K={r.K} nfe={r.nfe} "
              f"err_probe={r.err_probe:.3e} agree={a:.3f} "
              f"fused={r.fused_kernel}")


if __name__ == "__main__":
    main()

"""Docs link-and-freshness gate (tier-1): docs rot fails CI.

Three kinds of pin over README.md + docs/*.md:

  * python snippets actually run / their ``repro`` imports resolve —
    the README quickstart is executed, not pattern-matched;
  * every path-like cross-reference (``launch/scheduler.py``,
    ``docs/serving.md``, ``BENCH_*.json``) names a file that exists;
  * the flag tables in docs/serving.md and the argparse surface of
    ``launch/serve.py`` agree in BOTH directions — a flag added to the
    CLI without docs, or documented without existing, is a failure.
"""
import importlib
import os
import re
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
README = os.path.join(REPO_ROOT, "README.md")
DOCS_DIR = os.path.join(REPO_ROOT, "docs")
SERVE_PY = os.path.join(REPO_ROOT, "src", "repro", "launch", "serve.py")


def _doc_files():
    docs = [README] + sorted(
        os.path.join(DOCS_DIR, f) for f in os.listdir(DOCS_DIR)
        if f.endswith(".md"))
    assert len(docs) >= 3, "README.md + docs/{architecture,serving}.md"
    return docs


def _read(path):
    with open(path) as fh:
        return fh.read()


def _fenced(text, lang):
    """Fenced code blocks tagged ``lang``."""
    return re.findall(rf"```{lang}\n(.*?)```", text, re.DOTALL)


# ------------------------------------------------------ snippets run ----

def test_readme_quickstart_executes():
    """Every ```python block in the README is a RUNNABLE snippet —
    executed here in one shared namespace, so a renamed symbol or a
    changed signature fails CI, not a reader."""
    blocks = _fenced(_read(README), "python")
    assert blocks, "README lost its python quickstart"
    ns = {}
    for block in blocks:
        exec(compile(block, README, "exec"), ns)  # noqa: S102


@pytest.mark.parametrize("path", _doc_files(),
                         ids=[os.path.basename(p) for p in _doc_files()])
def test_snippet_imports_resolve(path):
    """``from repro.x import y`` / ``import repro.x`` lines inside any
    python snippet must resolve against the live package."""
    for block in _fenced(_read(path), "python"):
        for line in block.splitlines():
            m = re.match(r"\s*from\s+(repro[\w.]*)\s+import\s+(.+)", line)
            if m:
                mod = importlib.import_module(m.group(1))
                for name in m.group(2).split(","):
                    name = name.strip().split(" as ")[0].strip("()")
                    if name:
                        assert hasattr(mod, name), (path, line)
                continue
            m = re.match(r"\s*import\s+(repro[\w.]*)", line)
            if m:
                importlib.import_module(m.group(1))


def test_dotted_module_references_import():
    """Backticked/CLI module paths (``repro.launch.serve``,
    ``benchmarks.run``) must import — a moved module invalidates every
    command line that names it."""
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    for path in _doc_files():
        text = _read(path)
        for mod in set(re.findall(r"\b(repro(?:\.[a-z_0-9]+)+)\b", text)):
            importlib.import_module(mod)
        for mod in set(re.findall(r"\b(benchmarks\.[a-z_0-9]+)\b", text)):
            importlib.import_module(mod)


# ----------------------------------------------------- path freshness ----

# path-like tokens are checked when they contain a separator (bare file
# names like ``ops.py`` carry no unambiguous location); resolution tries
# the repo root, src/, and src/repro/ prefixes
_PATH_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_./-]*\.(?:py|md|json)\b")


def _resolves(ref):
    for base in ("", "src", os.path.join("src", "repro")):
        if os.path.exists(os.path.join(REPO_ROOT, base, ref)):
            return True
    return False


@pytest.mark.parametrize("path", _doc_files(),
                         ids=[os.path.basename(p) for p in _doc_files()])
def test_path_references_exist(path):
    text = _read(path)
    missing = []
    for ref in set(_PATH_RE.findall(text)):
        ref = ref.split("::")[0]
        if "/" not in ref or "*" in ref:
            continue
        if not _resolves(ref):
            missing.append(ref)
    assert not missing, (
        f"{os.path.basename(path)} references files that do not exist "
        f"(moved/renamed without a docs update?): {sorted(missing)}")


def test_bench_wildcard_targets_exist():
    """``BENCH_*.json`` in the docs is a real glob at the repo root."""
    import glob
    assert glob.glob(os.path.join(REPO_ROOT, "BENCH_*.json"))


# ------------------------------------------------------ flag freshness ----

# flags that are legitimately not launch/serve.py's (other CLIs, env)
_FLAG_ALLOW = {
    "--check", "--budget", "--only",           # benchmarks/run.py
    "--out",                                   # bench_* scripts
    "--chips",                                 # chip_smoke.py
    "--xla_force_host_platform_device_count",  # XLA_FLAGS env
}


def _serve_flags():
    flags = set(re.findall(r'add_argument\(\s*"(--[a-z][a-z0-9-]*)"',
                           _read(SERVE_PY)))
    assert flags, "could not parse launch/serve.py's argparse surface"
    return flags


def test_documented_flags_exist():
    """Every ``--flag`` token in README/docs names a real CLI flag."""
    declared = _serve_flags() | _FLAG_ALLOW
    for path in _doc_files():
        used = set(re.findall(r"--[a-z][a-z0-9_-]*", _read(path)))
        unknown = {u for u in used
                   if not any(u == d or u.startswith(d + "=")
                              for d in declared)}
        assert not unknown, (
            f"{os.path.basename(path)} documents flags that no CLI "
            f"declares: {sorted(unknown)}")


def test_serve_flags_are_documented():
    """The reverse direction: every flag launch/serve.py declares must
    appear in docs/serving.md (the operator guide is complete)."""
    serving = _read(os.path.join(DOCS_DIR, "serving.md"))
    undocumented = {f for f in _serve_flags() if f"`{f}`" not in serving}
    assert not undocumented, (
        "launch/serve.py flags missing from docs/serving.md: "
        f"{sorted(undocumented)}")


# --------------------------------------------------- cost accounting ----

def test_tick_accounting_prose_matches_live_oracle():
    """The Layer-4 tick-accounting prose in docs/architecture.md and the
    'Cost accounting' section in docs/serving.md describe the LIVE
    oracle: the documented `s*K` segment price and `probe_nfe` probe
    price are asserted against SequentialEvalOracle itself, and both
    unit strings the docs name must be the ones the implementations
    report."""
    from repro.launch.oracle import RooflineOracle, SequentialEvalOracle

    arch = _read(os.path.join(DOCS_DIR, "architecture.md"))
    serving = _read(os.path.join(DOCS_DIR, "serving.md"))

    # the prose names the oracle module and the `s*K` pricing rule
    assert "launch/oracle.py" in arch
    assert "`s*K`" in arch and "probe_nfe" in arch
    seq = SequentialEvalOracle()
    assert seq.segment_cost((8,), 5, 4, 3) == 15.0        # s=3, K=5
    assert seq.solve_cost((8,), 5, 4, 3) == 15.0
    assert seq.probe_cost((8,), 4, 2) == 2.0
    # batch-width free, as both docs claim
    assert seq.segment_cost((8,), 5, 4096, 3) == 15.0

    # unit strings in the docs are the ones the oracles report
    for doc in (arch, serving):
        assert "SequentialEvalOracle" in doc
        assert "RooflineOracle" in doc
    assert seq.unit == "sequential_evals"
    assert RooflineOracle.unit == "device_us"
    assert f"`{seq.unit}`" in serving
    assert f"`{RooflineOracle.unit}`" in serving

    # serving.md documents the tuned-config contract and the two fixed
    # accounting bugs
    assert "artifacts/tuned" in serving
    assert "occupied_steps" in serving
    assert "Cross-pool" in serving or "cross-pool" in serving


def test_wallclock_unit_prose_matches_live_tag():
    """The real-clock unit the BENCH_wallclock glossary names is the one
    the bench stamps (`launch/oracle.py::WALLCLOCK_UNIT`), and the
    overlap timeline the architecture doc draws names the live knobs."""
    from repro.launch.oracle import WALLCLOCK_UNIT

    serving = _read(os.path.join(DOCS_DIR, "serving.md"))
    arch = _read(os.path.join(DOCS_DIR, "architecture.md"))
    assert WALLCLOCK_UNIT == "wall_us"
    assert f"`{WALLCLOCK_UNIT}`" in serving
    assert "WALLCLOCK_UNIT" in serving
    # the documented pipeline phases exist as code
    from repro.launch.scheduler import InflightScheduler, _SlotPool
    assert "overlap=True" in arch
    for method in ("launch_segment", "retire_pending", "finalize_retired"):
        assert hasattr(_SlotPool, method)
    assert "_step_overlap" in arch or "one-segment" in arch.lower()
    assert "donate" in arch
    import inspect
    assert "donate" in inspect.signature(
        InflightScheduler.__init__).parameters


def test_kernel_pass_count_prose_matches_traffic_model():
    """Layer-1 prose claims the unfused update costs `stages + 3` jnp
    passes vs ONE fused memory pass — asserted against the live traffic
    model the kernel bench ships (benchmarks/bench_kernels.py), not
    against a copy of the arithmetic."""
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from benchmarks.bench_kernels import _traffic_model

    arch = _read(os.path.join(DOCS_DIR, "architecture.md"))
    assert "`stages + 3`" in arch
    for stages in (1, 2, 4):
        model = _traffic_model(stages, True, 1024)
        assert model["memory_passes_unfused"] == stages + 3
        assert model["memory_passes_fused"] == 1
        assert model["traffic_ratio"] > 1.0


def test_trace_counts_prose_matches_live_counter():
    """The docs lean on `TRACE_COUNTS` as the compile-count witness:
    verify it is live — a second same-shape fused solve must NOT add a
    kernel trace, and a new shape must add exactly one."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Integrator, get_tableau
    from repro.kernels.hyper_step.ops import TRACE_COUNTS

    arch = _read(os.path.join(DOCS_DIR, "architecture.md"))
    assert "TRACE_COUNTS" in arch
    integ = Integrator(get_tableau("euler"), fused=True)
    f = lambda s, z: -z                                    # noqa: E731
    z0 = jnp.asarray(np.ones((4, 7), np.float32))
    Ks_a = jnp.asarray([1, 2, 3, 4], jnp.int32)
    Ks_b = jnp.asarray([4, 4, 2, 1], jnp.int32)
    integ.solve_multirate(f, z0, (0.0, 1.0), Ks_a, 4)
    before = TRACE_COUNTS["fused_rk_update"]
    integ.solve_multirate(f, z0, (0.0, 1.0), Ks_b, 4)      # same shape
    assert TRACE_COUNTS["fused_rk_update"] == before, (
        "same-shape solve retraced the fused kernel")
    z1 = jnp.asarray(np.ones((4, 9), np.float32))          # new shape
    integ.solve_multirate(f, z1, (0.0, 1.0), Ks_a, 4)
    assert TRACE_COUNTS["fused_rk_update"] > before


def test_refinery_prose_matches_live_api():
    """The Layer-6 refinery prose (docs/architecture.md) and the
    'Online refinement' flag table + BENCH_refinery glossary
    (docs/serving.md) describe the LIVE loop: the named classes, hooks,
    and verdict keys are asserted against launch/refinery.py, both
    serving loops' swap surface, and benchmarks/run.py's check gate."""
    import inspect

    from repro.launch.engine import MultiRateEngine, validate_g_swap
    from repro.launch.refinery import (
        Refinery, RefineryConfig, ResidualLedger,
    )
    from repro.launch.scheduler import InflightScheduler

    arch = _read(os.path.join(DOCS_DIR, "architecture.md"))
    serving = _read(os.path.join(DOCS_DIR, "serving.md"))

    # the architecture doc names the refinery layer and its invariant
    assert "launch/refinery.py" in arch
    assert "ResidualLedger" in arch and "params-are-inputs" in arch
    assert "hot_swap_g" in arch and "TRACE_COUNTS" in arch

    # the documented swap surface is live on BOTH loops + the refinery
    for cls in (InflightScheduler, MultiRateEngine):
        assert hasattr(cls, "hot_swap_g")
        assert "ledger" in inspect.signature(cls.__init__).parameters
    for method in ("train_tick", "shadow_score", "maybe_promote",
                   "check_promoted", "tick", "flush", "status"):
        assert hasattr(Refinery, method)
    for attr in ("capture", "capture_pool", "sample_batch",
                 "holdout_batch", "flush"):
        assert hasattr(ResidualLedger, attr)
    assert validate_g_swap is not None

    # the flag table documents the knobs the refinery actually has
    cfg = RefineryConfig()
    assert cfg.steps_per_tick == 2 and cfg.shadow_every == 100
    for flag in ("--refine", "--capture-rate", "--ledger-cap",
                 "--refine-steps", "--shadow-every", "--ledger-out",
                 "--progress-every", "--refine-dir"):
        assert f"`{flag}`" in serving, f"{flag} missing from serving.md"

    # the BENCH_refinery glossary names the verdict keys --check gates
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from benchmarks.run import BENCH_REQUIRED, _check_refinery_section
    assert "BENCH_refinery.json" in BENCH_REQUIRED
    assert "BENCH_refinery.json" in serving
    for key in ("refined_beats_frozen", "equal_nfe", "capture_parity",
                "shadow_gate_clean"):
        assert f"`{key}`" in serving, f"verdict key {key} undocumented"
    # the gate function rejects an empty file shape (it is live)
    assert _check_refinery_section("BENCH_refinery.json", [])


def test_flow_tier_prose_matches_live_router():
    """The K=0 flow-tier flag table + BENCH_flow glossary (serving.md)
    and the three-tier ladder diagram (architecture.md) describe the
    LIVE router: the documented thresholds are TierRouter's actual
    defaults, the tier is actually off by default on EngineConfig, the
    named swap surface exists on both loops, and the verdict keys are
    the ones benchmarks/run.py gates."""
    import inspect

    from repro.core.controllers import TierRouter
    from repro.core.train import FlowTrainConfig, train_flowhead
    from repro.launch.engine import EngineConfig, MultiRateEngine
    from repro.launch.scheduler import InflightScheduler

    arch = _read(os.path.join(DOCS_DIR, "architecture.md"))
    serving = _read(os.path.join(DOCS_DIR, "serving.md"))

    # the documented default thresholds are the live ones
    router = TierRouter()
    assert router.flow_threshold == 0.25 and router.hyper_k_max == 4
    assert "`0.25`" in serving and "TierRouter" in serving
    assert "hyper_k_max" in serving
    # ...and the tier really is off unless asked for
    assert EngineConfig().flow_threshold == 0.0

    # architecture.md draws the ladder with the live pieces
    for token in ("TierRouter", "core/flowhead.py", "FLOW TIER",
                  "escalated", "min(buckets)"):
        assert token in arch, f"{token!r} missing from architecture.md"

    # the documented swap/accounting surface is live on BOTH loops
    for cls in (InflightScheduler, MultiRateEngine):
        assert hasattr(cls, "hot_swap_flow")
        assert "nfe_flow" in inspect.getsource(
            sys.modules[cls.__module__])
    assert train_flowhead is not None
    assert FlowTrainConfig().relative is True

    # the flag table documents the real CLI surface
    for flag in ("--flow-ckpt", "--flow-rank", "--flow-threshold"):
        assert f"`{flag}`" in serving, f"{flag} missing from serving.md"

    # the BENCH_flow glossary names the verdict keys --check gates
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from benchmarks.run import BENCH_REQUIRED, _check_flow_section
    assert "BENCH_flow.json" in BENCH_REQUIRED
    assert "BENCH_flow.json" in serving
    for key in ("three_tier_dominates", "flow_disabled_parity",
                "escalation_accounted", "zero_hang"):
        assert f"`{key}`" in serving, f"verdict key {key} undocumented"
    # the gate function rejects an empty file shape (it is live)
    assert _check_flow_section("BENCH_flow.json", [])


def test_failure_semantics_prose_matches_live_enum():
    """The 'Failure semantics' status glossary in docs/serving.md is
    asserted against the LIVE terminal-status enum and retry defaults —
    a new status or a changed retry budget must update the docs."""
    from repro.distributed.fault import FaultInjector, RetryPolicy
    from repro.launch.engine import STATUSES, QueueFull  # noqa: F401

    serving = _read(os.path.join(DOCS_DIR, "serving.md"))
    arch = _read(os.path.join(DOCS_DIR, "architecture.md"))
    assert "Failure semantics" in serving

    # every live status appears backticked in the glossary, and the
    # glossary table has exactly one row per status (no stale rows)
    section = serving.split("Failure semantics", 1)[1]
    for status in STATUSES:
        assert f"`{status}`" in section, f"status {status!r} undocumented"
    table_rows = re.findall(r"^\| `(\w+)` \|", section, re.MULTILINE)
    assert sorted(table_rows) == sorted(STATUSES), table_rows

    # the documented retry defaults are the live ones
    policy = RetryPolicy()
    assert policy.max_retries == 1
    assert policy.retry_statuses == ("diverged",)
    assert "max_retries=1" in section
    assert '("diverged",)' in section or "(\"diverged\",)" in section

    # the chaos-source sites the docs name exist and are disarmed by
    # default (a bare injector must be a no-op — fault-free parity)
    inj = FaultInjector()
    assert inj.nan_uid_frac == 0.0 and inj.drop_flag_p == 0.0 \
        and inj.straggle_tick_frac == 0.0
    for site in ("corrupt_admission", "drop_retire_flags",
                 "inflate_segment_cost"):
        assert hasattr(inj, site)

    # architecture.md's meta-layer note matches the live 3-row layout
    assert "3×B" in arch and "nonfinite" in arch
    assert "3×B" in section or "3×B" in serving

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.

"""Multi-pod dry-run: AOT lower + compile every (arch x shape) cell on the
production meshes, and record memory/cost/collective analysis for the
roofline (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_8b --shape train_4k \
      [--multi-pod] [--seq-shard] [--remat full] [--microbatches 4]
"""
import argparse
import json
import re
import sys
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, cell_is_applicable, get
from repro.distributed import sharding as shd
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.launch.steps import (
    StepSettings, data_shardings, input_specs, make_prefill_step,
    make_serve_step, make_train_step,
)

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")

# Per-arch training-step settings (microbatching + remat sized for HBM).
TRAIN_SETTINGS = {
    "nemotron_4_340b": StepSettings(microbatches=16, remat="full",
                                    seq_shard=True, fsdp=True,
                                    moment_dtype="bfloat16",
                                    acc_dtype="bfloat16"),
    "llama4_maverick_400b_a17b": StepSettings(microbatches=8, remat="full",
                                              seq_shard=True, fsdp=True,
                                              moment_dtype="bfloat16",
                                              acc_dtype="bfloat16"),
    "mistral_nemo_12b": StepSettings(microbatches=4, remat="full"),
    "qwen3_8b": StepSettings(microbatches=4, remat="full"),
    "whisper_base": StepSettings(microbatches=1, remat="dots"),
    "_default": StepSettings(microbatches=4, remat="full"),
}

_COLL_RE = re.compile(
    r"(\S+?)\s*=\s*(\S+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.I)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def collective_bytes(hlo_text: str):
    """Sum output bytes of every collective op in optimized HLO, per class.

    Convention: bytes counted are the (per-participating-device) op output
    — a consistent proxy for link traffic across collective kinds.
    """
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(3).lower()
        if "-done(" in line:  # avoid double-count of async pairs
            continue
        ty = m.group(2)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(ty):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[kind] += nbytes
        counts[kind] += 1
    return out, counts


def _attach(specs, shardings):
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        specs, shardings)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             settings: StepSettings = None, verbose: bool = True,
             mesh=None):
    cfg = get(arch)
    shape = SHAPES[shape_name]
    ok, reason = cell_is_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "SKIP", "reason": reason}
    settings = settings or TRAIN_SETTINGS.get(arch, TRAIN_SETTINGS["_default"])
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with jax.set_mesh(mesh):
        if settings.seq_shard and shape.kind != "decode":
            shd.set_activation_sharding(batch_axes(mesh), seq_axis="model")
        else:
            shd.set_activation_sharding(batch_axes(mesh))
        # q-chunked exact attention for long sequences: bounds the score
        # buffer to (B, H, 512, S) — XLA-level flash analogue (nn/attention)
        from repro.nn.attention import set_attention_chunking
        if shape.kind != "decode" and shape.seq_len >= 4096:
            set_attention_chunking(512)
        if settings.fsdp:
            shd.set_param_resharding(mesh)
        try:
            specs = input_specs(cfg, shape)
            d_sh = data_shardings(mesh, cfg, specs)
            specs = _attach(specs, d_sh)
            if shape.kind == "train":
                step, opt, (a_p, a_o, p_sh, o_sh) = make_train_step(
                    cfg, settings, mesh)
                a_params = _attach(a_p, p_sh)
                a_opt = _attach(a_o, o_sh)
                step0 = jax.ShapeDtypeStruct((), jnp.int32,
                                             sharding=NamedSharding(mesh, P()))
                lowered = step.lower(a_params, a_opt, step0, specs)
            elif shape.kind == "prefill":
                step, (a_p, p_sh) = make_prefill_step(cfg, settings, mesh)
                lowered = step.lower(_attach(a_p, p_sh), specs)
            else:
                step, (a_p, p_sh) = make_serve_step(cfg, mesh, settings)
                lowered = step.lower(_attach(a_p, p_sh), specs["token"],
                                     specs["caches"], specs["cur_index"])
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
            coll, coll_counts = collective_bytes(hlo)
            res = {
                "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "OK",
                "compile_s": round(time.time() - t0, 1),
                "settings": dataclass_dict(settings),
                "n_devices": int(mesh.size),
                "flops": float(cost.get("flops", -1.0)),
                "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
                "memory": {
                    "argument_bytes": int(mem.argument_size_in_bytes),
                    "output_bytes": int(mem.output_size_in_bytes),
                    "temp_bytes": int(mem.temp_size_in_bytes),
                    "generated_code_bytes": int(
                        mem.generated_code_size_in_bytes),
                    "alias_bytes": int(mem.alias_size_in_bytes),
                },
                "collective_bytes": coll,
                "collective_counts": coll_counts,
            }
            if verbose:
                print(f"[OK] {arch} x {shape_name} "
                      f"({'2x16x16' if multi_pod else '16x16'}) "
                      f"compile={res['compile_s']}s "
                      f"flops={res['flops']:.3e} "
                      f"temp/dev={mem.temp_size_in_bytes / 2**30:.2f}GiB "
                      f"coll={sum(coll.values()) / 2**30:.2f}GiB")
            return res
        except Exception as e:
            traceback.print_exc()
            return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                    "status": "FAIL", "error": f"{type(e).__name__}: {e}"}
        finally:
            shd.clear_activation_sharding()
            shd.clear_param_resharding()
            set_attention_chunking(None)


def dataclass_dict(s: StepSettings):
    import dataclasses
    return dataclasses.asdict(s)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--seq-shard", action="store_true", default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--int8-dispatch", action="store_true")
    ap.add_argument("--ep-data", action="store_true")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    from repro.models.lm import set_perf_options
    from repro.distributed.sharding import set_ep_axis
    if args.int8_dispatch:
        set_perf_options(int8_dispatch=True)
    if args.kv_int8:
        set_perf_options(kv_int8=True)
    if args.ep_data:
        set_ep_axis("data")
    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) \
        else [args.multi_pod]

    results = []
    mesh_cache = {}
    for mp in meshes:
        if mp not in mesh_cache:
            mesh_cache[mp] = make_production_mesh(multi_pod=mp)
        for arch in archs:
            for shape in shapes:
                settings = TRAIN_SETTINGS.get(arch,
                                              TRAIN_SETTINGS["_default"])
                overrides = {}
                if args.seq_shard is not None:
                    overrides["seq_shard"] = args.seq_shard
                if args.remat:
                    overrides["remat"] = args.remat
                if args.microbatches:
                    overrides["microbatches"] = args.microbatches
                if overrides:
                    import dataclasses
                    settings = dataclasses.replace(settings, **overrides)
                res = run_cell(arch, shape, mp, settings,
                               mesh=mesh_cache[mp])
                results.append(res)
                tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}" + \
                    (f"__{args.tag}" if args.tag else "")
                with open(os.path.join(ARTIFACT_DIR, tag + ".json"), "w") as f:
                    json.dump(res, f, indent=1)

    # only --all owns summary.json (single-cell reruns must not clobber)
    default_name = "summary.json" if args.all else "summary_partial.json"
    out = args.out or os.path.join(ARTIFACT_DIR, default_name)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    n_ok = sum(r["status"] == "OK" for r in results)
    n_skip = sum(r["status"] == "SKIP" for r in results)
    n_fail = sum(r["status"] == "FAIL" for r in results)
    print(f"\n== dry-run: {n_ok} OK, {n_skip} SKIP (documented), "
          f"{n_fail} FAIL ==")
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()

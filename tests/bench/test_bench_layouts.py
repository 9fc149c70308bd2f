"""The layout layer (``spec.load_layout``, ``bench/layouts/``).

The dense decoder reads as it did when its layers were written into the
harness itself: weights, the reference's terminal states and logits, and
FLOP counts equal to values recorded from that code (SHA-256 of every
leaf's bytes, in tree order). A model that is not dense, added as files
only (``tiny.py``), has its FLOPs counted by its layout. A configuration
without a layout, or with a layout that misses a leaf of the program's
tree, is refused."""
import hashlib
import importlib.util
import json
import os
import types

import jax
import numpy as np
import pytest

import counts
import reference
import spec
import weights
from tiny import BENCH, CELL, MOE_CELL, MOE_CONFIG, copy_with_tiny_cell

SEED = 2 ** 31 + 7

# recorded from the harness before layouts, at rehearsal widths
WEIGHTS = {
    "qwen3_4b":
        "15f7463360eab947a5115fd321a37b43ee093fd3d3e162d30bffd25607bd8001",
    "mistral_nemo_12b":
        "49c06dea07824e49ba060a284c1778170680435f812d457af5bfc3a0608e93c7",
}
FLOPS = {("qwen3_4b", 9): 1347785064448.0, ("qwen3_4b", 10): 1453284392960.0,
         ("qwen3_4b", 18): 2297279021056.0,
         ("mistral_nemo_12b", 9): 3219115737088.0,
         ("mistral_nemo_12b", 10): 3500440289280.0,
         ("mistral_nemo_12b", 18): 5751036706816.0}
# the tiny cell's configuration, 4 seeded 32-token prompts:
# (K, terminal states, logits, fp8 logits)
SERVERS = {
    "hyper_euler.auto": (
        {"solver": "hyper_euler", "controller": "auto", "tol": 4.0,
         "buckets": [9, 18, 36]}, [18] * 4,
        "3d3dd18da972eaaf88d33c5b66c5c7dff5306c4e7d218f3e8a3fe6f5fd3da450",
        "996a94911adb26eaf12b831e7cec7dd5e50eb0a88025634dd58b503662d8550f",
        "c7b764ca7289a2ad66f1dbfbbea60cc0b0d689a44171627cfae93f7fe26a9d5a"),
    "euler.fixed": (
        {"solver": "euler", "controller": "fixed", "fixed_K": 9,
         "buckets": [9]}, [9] * 4,
        "c7ed556167c3c84e2e248e3319023ac82381df5ad17b540fffcd522da9af9663",
        "87effd6b5171a4cd2672d79ea9bfce57a6e00effe8b8d83ec6a42f11c8401738",
        "3b2999b985c86e9f33f150357e36851ed44fa048699a487116ae530c290a6f8e"),
}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _rehearsed(name):
    """(layout, sizes, ArchConfig) of ``configs/<name>.json`` at
    rehearsal widths."""
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        config = json.load(f)
    layout = spec.load_layout(config["layout"])
    sizes = layout.rehearse(config)
    return layout, sizes, spec.arch_config(sizes, layout)


@pytest.mark.parametrize("name", sorted(WEIGHTS))
def test_dense_weights_as_recorded(name):
    layout, _, cfg = _rehearsed(name)
    assert layout.__name__ == "bench_layout_dense_decoder"
    assert _digest(weights.model_weights(layout, cfg, SEED)) == WEIGHTS[name]


@pytest.mark.parametrize("server", sorted(SERVERS))
def test_dense_reference_as_recorded(server):
    layout, m, cfg = _rehearsed(CELL["config"])
    w = weights.model_weights(layout, cfg, SEED)
    gw = weights.g_weights(cfg, SEED, 32, 9.0)
    tokens = np.random.default_rng(1234).integers(0, m["vocab_size"],
                                                  (4, 32))
    srv, K, states, logits, fp8 = SERVERS[server]
    h, got = reference.solve(layout, w, gw, m, srv, tokens)
    assert [int(k) for k in got] == K
    assert _digest(h) == states
    assert _digest(reference.logits(layout, w, m, h)) == logits
    assert _digest(reference.logits(layout, w, m, h, precision="fp8")) == fp8


@pytest.mark.parametrize("name, nfe", sorted(FLOPS))
def test_dense_request_flops_as_recorded(name, nfe):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        m = json.load(f)
    layout = spec.load_layout(m["layout"])
    assert counts.request_flops(layout, m, 512, nfe) == FLOPS[(name, nfe)]


def test_mfu_reads_the_layout_counts(tmp_path):
    bench = copy_with_tiny_cell(str(tmp_path))
    layout = spec.load_layout("dense_moe", bench)
    S, nfe = 512, 4
    comp = [{"nfe": nfe, "status": "ok"}] * 3 + [{"nfe": nfe,
                                                  "status": "failed"}]
    ctx = types.SimpleNamespace(
        completions=comp, sizes=MOE_CONFIG, layout=layout, prompt_len=S,
        window_s=2.0, chips=1, peaks=counts.peaks("TPU v5 lite"))
    mfu = importlib.util.spec_from_file_location(
        "metric_mfu", os.path.join(bench, "metrics", "mfu.py"))
    mod = importlib.util.module_from_spec(mfu)
    mfu.loader.exec_module(mod)
    # per token, OLMoE widths (d 2048, 16 heads of 128, MHA): q/k/v/o
    # projections, causal core, dense SwiGLU (d_ff 1024); a moe layer's
    # router over 4 experts and 2 SwiGLU experts of width 1024
    attn = 2 * 2048 * 6144 + 2 * 2048 * 2048 + 4 * 16 * 128 * (S + 1) / 2
    dense_layer = attn + 6 * 2048 * 1024
    moe_layer = attn + 2 * 2048 * 4 + 2 * 6 * 2048 * 1024
    readout = 2 * 2048 * 50304
    # nfe groups of [dense, moe], then the dense tail layer, then readout
    per_request = S * (nfe * (dense_layer + moe_layer) + dense_layer
                       + readout)
    assert layout.n_fields(MOE_CONFIG) == 2
    assert mod.read(ctx) == pytest.approx(
        100 * 3 * per_request / (2.0 * 197e12), rel=1e-12)


SHORT_LAYOUT = '''
import os
import spec

_full = spec.load_layout("dense_moe", os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
globals().update({k: v for k, v in vars(_full).items()
                  if not k.startswith("__")})


def shapes(cfg):
    out = _full.shapes(cfg)
    del out[("tail", "t0", "ffn", "wd", "kernel")]
    return out
'''


def _no_layout(bench):
    with open(os.path.join(bench, "configs", "qwen3_4b.json")) as f:
        config = json.load(f)
    del config["layout"]
    return {"configs/no_layout.json": json.dumps(config),
            "cells/refused.json": json.dumps(dict(CELL, config="no_layout"))}


def _short_layout(bench):
    return {"layouts/dense_moe_short.py": SHORT_LAYOUT,
            "configs/short.json": json.dumps(
                dict(MOE_CONFIG, layout="dense_moe_short")),
            "cells/refused.json": json.dumps(dict(MOE_CELL, config="short"))}


@pytest.mark.parametrize("files, says", [
    (_no_layout, "configs/no_layout.json names no layout"),
    (_short_layout, "weights do not fit the program's parameter tree"),
])
def test_refusals(tmp_path, files, says):
    import run

    bench = copy_with_tiny_cell(str(tmp_path))
    for rel, text in files(bench).items():
        with open(os.path.join(bench, rel), "w") as f:
            f.write(text)
    with pytest.raises(SystemExit, match=says):
        run.run("refused", 3, 1.0, False, rehearse=True, bench_dir=bench)

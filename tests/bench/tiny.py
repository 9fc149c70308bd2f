"""Tiny cells, written into a copy of ``bench/`` the way a later change
adds a cell: one JSON file, no edit to any file that is there; and a
model that is not dense, added the way a later change adds one: a
layout module, a configuration and a cell. Their limits are set from
CPU readings at this size (``PERF.md``)."""
import json
import os
import shutil

import counts

BENCH = os.path.dirname(os.path.abspath(counts.__file__))
REPO = os.path.dirname(BENCH)

CELL = {
    "config": "qwen3_4b",
    "traffic": "backlog",
    "traffic_params": {"prompt_len": 32},
    "chips": 1,
    "why": "CPU rehearsal of the offline cell at cut widths",
    "server": {"solver": "hyper_euler", "g_rank": 32, "controller": "auto",
               "tol": 4.0, "g_out_std": 9.0, "buckets": [9, 18, 36],
               "slots": 4, "seg": 2, "fused": True, "mesh": None},
    "check": {"sample_every": 2, "sample_most": 8,
              "positions_per_request": 64, "rows_per_request": 4,
              "limits": {"max_logit_gap": 0.35, "logit_rel_err": 0.1,
                         "k_mismatch": 0, "not_ok": 0}},
    "trace": {"start_s": 0.5, "seconds": 1},
}


# the same at a fixed Poisson rate: slots refill a few at a time
OPEN_CELL = dict(CELL, traffic="poisson",
                 traffic_params={"prompt_len": 32, "rate_per_s": 6.0})

# the slot pool sharded over four devices
MESH_CELL = dict(CELL, chips=4, server=dict(CELL["server"], slots=8, mesh=4))

# the program's moe block kind interleaved with dense layers, [dense, moe]:
# two groups and one dense tail layer; OLMoE's widths (cut by the layout's
# rehearsal) with 4 experts, top-2, and a capacity of experts / top-k so
# that no token is dropped. In float32: in bfloat16 the program's stream
# drifts from the reference's far enough to pick another expert where the
# second and third are near a tie, and one such token reads as much as
# the control does (PERF.md)
MOE_LAYOUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "layout_dense_moe.py")
MOE_CONFIG = {
    "name": "tiny_moe",
    "source": "https://huggingface.co/allenai/OLMoE-1B-7B-0924",
    "program_arch": "olmoe_1b_7b",
    "layout": "dense_moe",
    "num_hidden_layers": 5,
    "decoder_sparse_step": 2,
    "hidden_size": 2048,
    "num_attention_heads": 16,
    "num_key_value_heads": 16,
    "head_dim": 128,
    "intermediate_size": 1024,
    "moe_intermediate_size": 1024,
    "num_experts": 4,
    "num_experts_per_tok": 2,
    "norm_topk_prob": True,
    "capacity_factor": 2.0,
    "vocab_size": 50304,
    "hidden_act": "silu",
    "rope_theta": 10000,
    "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "qk_norm": True,
    "torch_dtype": "float32",
}
MOE_CELL = dict(
    CELL, config="tiny_moe",
    why="CPU rehearsal of a layout that is not dense: [dense, moe] groups "
        "and a dense tail layer",
    server={"solver": "euler", "controller": "fixed", "fixed_K": 4,
            "buckets": [4], "slots": 4, "seg": 2, "fused": True,
            "mesh": None},
    check=dict(CELL["check"], limits={"max_logit_gap": 0.05,
                                      "logit_rel_err": 1e-3,
                                      "k_mismatch": 0, "not_ok": 0}))


def copy_with_tiny_cell(root: str) -> str:
    """``root``/bench: a copy of the benchmark plus the tiny cells, with
    ``BENCHMARK.json`` beside it and the program at ``root``/src. Returns
    the copy's bench directory."""
    bench = os.path.join(root, "bench")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    with open(os.path.join(bench, "cells", "tiny.json"), "w") as f:
        json.dump(CELL, f)
    with open(os.path.join(bench, "cells", "tiny_open.json"), "w") as f:
        json.dump(OPEN_CELL, f)
    with open(os.path.join(bench, "cells", "tiny_mesh4.json"), "w") as f:
        json.dump(MESH_CELL, f)
    shutil.copy(MOE_LAYOUT, os.path.join(bench, "layouts", "dense_moe.py"))
    with open(os.path.join(bench, "configs", "tiny_moe.json"), "w") as f:
        json.dump(MOE_CONFIG, f)
    with open(os.path.join(bench, "cells", "tiny_moe.json"), "w") as f:
        json.dump(MOE_CELL, f)
    return bench

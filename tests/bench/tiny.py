"""A tiny cell, written into a copy of ``bench/`` the way a later change
adds a cell: one JSON file, no edit to any file that is there. Its
limits are set from CPU readings at this size (``PERF.md``)."""
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
    return bench

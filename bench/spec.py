"""Find a cell, its configuration and its traffic mix by name.

Everything that belongs to one cell, one configuration or one traffic mix
is a JSON file of its own under this directory:

    cells/<cell>.json       configuration, traffic, server settings, check
    configs/<config>.json   the model as it is run, with its source
    traffic/<mix>.json      parameters that ``traffic.py`` reads

A later cell is added by adding files; no code here names a cell.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

# keys of a configuration file, as the model's own config.json names
# them, and the program's ArchConfig field each one sets
ARCH_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv",
    "head_dim": "d_head",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "hidden_act": "act",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "qk_norm": "qk_norm",
}


def _load(kind: str, name: str, bench_dir: str) -> Dict[str, Any]:
    path = os.path.join(bench_dir, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise SystemExit(f"bench: no {kind[:-1]} named {name!r} "
                         f"(looked for {path})")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]      # configs/<config>.json
    traffic: Dict[str, Any]     # traffic/<mix>.json, overridden by the cell
    server: Dict[str, Any]
    check: Dict[str, Any]
    trace: Dict[str, Any]


def load_cell(name: str, bench_dir: str = BENCH_DIR) -> Cell:
    c = _load("cells", name, bench_dir)
    traffic = dict(_load("traffic", c["traffic"], bench_dir))
    traffic.update(c.get("traffic_params", {}))
    return Cell(name=name, chips=int(c["chips"]),
                config=_load("configs", c["config"], bench_dir),
                traffic=traffic, server=dict(c["server"]),
                check=dict(c["check"]), trace=dict(c.get("trace", {})))


def model_sizes(config: Dict[str, Any], rehearse: bool = False
                ) -> Dict[str, Any]:
    """The configuration as it is run. ``rehearse`` keeps the layout
    (depth, head ratio, tying, qk-norm, dtype) and cuts every width to a
    size the CPU runs in seconds; no result is ever reported for it."""
    sizes = dict(config)
    if rehearse:
        ratio = sizes["num_attention_heads"] // sizes["num_key_value_heads"]
        sizes.update(hidden_size=64, num_key_value_heads=2,
                     num_attention_heads=2 * ratio, head_dim=16,
                     intermediate_size=128, vocab_size=512)
    return sizes


def arch_config(sizes: Dict[str, Any]):
    """The program's ArchConfig: the registered architecture with every
    size of ``sizes`` put in its place."""
    from repro.configs import get

    fields = {ARCH_KEYS[k]: v for k, v in sizes.items() if k in ARCH_KEYS}
    fields["dtype"] = fields["param_dtype"] = sizes["torch_dtype"]
    return dataclasses.replace(get(sizes["program_arch"]), **fields)

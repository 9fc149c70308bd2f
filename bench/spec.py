"""Find a cell, its configuration, its layout and its traffic mix by name.

Everything that belongs to one cell, one configuration, one layout or one
traffic mix is a file of its own under this directory:

    cells/<cell>.json       configuration, traffic, server settings, check
    configs/<config>.json   the model as it is run, with its source and
                            its layout ("layout": "<layout>")
    layouts/<layout>.py     what the model's layers are (below)
    traffic/<mix>.json      parameters that ``traffic.py`` reads

A later cell or model is added by adding files; no code here names a
cell, a configuration or a layout.

A layout module says everything that depends on the model's layers:

    ARCH_KEYS                  configuration key -> ArchConfig field, beside
                               ``SHARED_KEYS``
    rehearse(sizes)            the CPU cut: layout kept, widths cut
    shapes(cfg)                leaf path -> (shape, kind) of the program's
                               whole parameter tree, groups and tail
    n_fields(m)                the program's number of groups
    field(w, h, idx, m, p)     group ``idx``'s output on h (B, S, d): float32,
                               plain jax.numpy at HIGHEST, every linear
                               layer through ``reference._linear(.., p)``
    tail(w, h, m, p)           the discrete tail layers before the final norm
    field_eval_flops(m, S)     model FLOPs of one group on S tokens
    tail_flops(m, S)           and of the tail layers
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types
from typing import Any, Dict

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

# keys of a configuration file that every layout has, as the model's own
# config.json names them, and the program's ArchConfig field each one
# sets; a layout's ``ARCH_KEYS`` adds its own
SHARED_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "vocab_size": "vocab",
    "tie_word_embeddings": "tie_embeddings",
}

# layout modules loaded in this process, by path: the reference's jitted
# functions take the module as a static argument, so a second run in one
# process (``control.py``) finds them compiled
_LAYOUTS: Dict[str, types.ModuleType] = {}


def _load(kind: str, name: str, bench_dir: str) -> Dict[str, Any]:
    path = os.path.join(bench_dir, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise SystemExit(f"bench: no {kind[:-1]} named {name!r} "
                         f"(looked for {path})")
    with open(path) as f:
        return json.load(f)


def load_layout(name: str, bench_dir: str = BENCH_DIR) -> types.ModuleType:
    """``layouts/<name>.py`` of ``bench_dir``, loaded by its path."""
    path = os.path.join(bench_dir, "layouts", f"{name}.py")
    if path not in _LAYOUTS:
        if not os.path.isfile(path):
            raise SystemExit(f"bench: no layout named {name!r} "
                             f"(looked for {path})")
        spec = importlib.util.spec_from_file_location(
            f"bench_layout_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LAYOUTS[path] = mod
    return _LAYOUTS[path]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]      # configs/<config>.json
    layout: types.ModuleType    # layouts/<config["layout"]>.py
    traffic: Dict[str, Any]     # traffic/<mix>.json, overridden by the cell
    server: Dict[str, Any]
    check: Dict[str, Any]
    trace: Dict[str, Any]


def load_cell(name: str, bench_dir: str = BENCH_DIR) -> Cell:
    c = _load("cells", name, bench_dir)
    traffic = dict(_load("traffic", c["traffic"], bench_dir))
    traffic.update(c.get("traffic_params", {}))
    config = _load("configs", c["config"], bench_dir)
    if "layout" not in config:
        # no default: the dense reference under another model would be
        # wrong without anyone seeing it
        raise SystemExit(
            "bench: configuration "
            f"{os.path.join(bench_dir, 'configs', c['config'] + '.json')} "
            "names no layout; add \"layout\": \"<name>\" for "
            "layouts/<name>.py")
    return Cell(name=name, chips=int(c["chips"]), config=config,
                layout=load_layout(config["layout"], bench_dir),
                traffic=traffic, server=dict(c["server"]),
                check=dict(c["check"]), trace=dict(c.get("trace", {})))


def model_sizes(cell: Cell, rehearse: bool = False) -> Dict[str, Any]:
    """The configuration as it is run. ``rehearse`` is the layout's cut:
    the layout kept, every width cut to a size the CPU runs in seconds;
    no result is ever reported for it."""
    sizes = dict(cell.config)
    return cell.layout.rehearse(sizes) if rehearse else sizes


def arch_config(sizes: Dict[str, Any], layout: types.ModuleType):
    """The program's ArchConfig: the registered architecture with every
    size of ``sizes`` that the shared keys and the layout's keys name put
    in its place."""
    from repro.configs import get

    keys = {**SHARED_KEYS, **layout.ARCH_KEYS}
    fields = {keys[k]: v for k, v in sizes.items() if k in keys}
    fields["dtype"] = fields["param_dtype"] = sizes["torch_dtype"]
    return dataclasses.replace(get(sizes["program_arch"]), **fields)

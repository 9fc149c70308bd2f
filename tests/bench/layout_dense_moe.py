"""A layout that is not the dense decoder: the program's ``moe`` block
kind, every ``decoder_sparse_step``-th layer, the others dense.

    group = [dense] * (step - 1) + [moe]       n_fields = layers // step
    tail  = the group's first (layers % step) kinds, each on its own

A moe layer is the dense layer with its feed-forward half replaced by
a routed sum of SwiGLU experts:

    p = softmax(x Wr);  top-k of p, renormalised when norm_topk_prob
    h <- h + sum_{e in top-k} p_e SwiGLU_e(x)        x = RMSNorm(h)

``tests/bench/tiny.py`` writes this file into a copy of ``bench/`` as
``layouts/dense_moe.py``, beside a configuration and a cell: a model
that is not dense, added as files only.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

import spec
from reference import _linear, _rmsnorm

dense = spec.load_layout(
    "dense_decoder", os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))))

ARCH_KEYS = dict(dense.ARCH_KEYS,
                 num_experts="n_experts",
                 num_experts_per_tok="top_k",
                 moe_intermediate_size="d_ff_expert",
                 decoder_sparse_step="moe_every",
                 capacity_factor="capacity_factor")


def rehearse(sizes: dict) -> dict:
    """The dense cut, and the expert width; experts, top-k and depth
    kept."""
    return dict(dense.rehearse(sizes), moe_intermediate_size=32)


def _pattern(step: int):
    return ("dense",) * (step - 1) + ("moe",)


def _counts(layers: int, step: int):
    """(groups, tail layers)."""
    return divmod(layers, step)


def _layer_shapes(cfg, kind: str, prefix: tuple, lead: tuple):
    out = dense.layer_shapes(cfg, prefix, lead)
    if kind == "dense":
        return out
    out = {k: v for k, v in out.items() if k[len(prefix)] != "ffn"}
    d, E, F = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    out[prefix + ("moe", "router", "kernel")] = (lead + (d, E), d)
    out[prefix + ("moe", "wi")] = (lead + (E, d, F), d)
    out[prefix + ("moe", "wg")] = (lead + (E, d, F), d)
    out[prefix + ("moe", "wd")] = (lead + (E, F, d), F)
    return out


def shapes(cfg):
    """Leaf path -> (shape, kind) of the whole parameter tree."""
    d, V = cfg.d_model, cfg.vocab
    pattern = _pattern(cfg.moe_every)
    groups, tail_layers = _counts(cfg.n_layers, cfg.moe_every)
    out = {("embed", "table"): ((V, d), 1.0),
           ("ln_f", "scale"): ((d,), "norm")}
    for i, kind in enumerate(pattern):
        out.update(_layer_shapes(cfg, kind, ("groups", f"b{i}"), (groups,)))
    for i in range(tail_layers):
        out.update(_layer_shapes(cfg, pattern[i], ("tail", f"t{i}"), ()))
    if not cfg.tie_embeddings:
        out[("head", "kernel")] = ((d, V), d)
    return out


def n_fields(m: dict) -> int:
    return _counts(m["num_hidden_layers"], m["decoder_sparse_step"])[0]


def experts(p, x, m, precision: str):
    """The routed half on the normed stream x (B, S, d): every expert on
    every token, weighted by its gate, which is 0 off the top-k."""
    E, k = m["num_experts"], m["num_experts_per_tok"]
    probs = jax.nn.softmax(_linear(x, p["router"]["kernel"], precision), -1)
    top, idx = jax.lax.top_k(probs, k)
    if m["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, E) * top[..., None], -2)
    y = jnp.zeros_like(x)
    for e in range(E):
        ffn = {n: {"kernel": p[n][e]} for n in ("wi", "wg", "wd")}
        y = y + gates[..., e:e + 1] * dense.swiglu(ffn, x, precision)
    return y


def moe_block(p, h, m, precision: str):
    eps = m["rms_norm_eps"]
    h = h + dense.attention(p["attn"], _rmsnorm(h, p["ln1"]["scale"], eps),
                            m, precision)
    x = _rmsnorm(h, p["ln2"]["scale"], eps)
    return h + experts(p["moe"], x, m, precision)


BLOCKS = {"dense": dense.block, "moe": moe_block}


def field(w, h, idx, m, precision: str):
    g = dense.group(w, idx)
    for i, kind in enumerate(_pattern(m["decoder_sparse_step"])):
        h = BLOCKS[kind](g[f"b{i}"], h, m, precision)
    return h


def tail(w, h, m, precision: str):
    pattern = _pattern(m["decoder_sparse_step"])
    for i in range(_counts(m["num_hidden_layers"],
                           m["decoder_sparse_step"])[1]):
        h = BLOCKS[pattern[i]](w["tail"][f"t{i}"], h, m, precision)
    return h


def _layer_flops(kind: str, m: dict, S: int) -> float:
    if kind == "dense":
        return dense.field_eval_flops(m, S)
    d = m["hidden_size"]
    router = 2 * S * d * m["num_experts"]
    routed = m["num_experts_per_tok"] * 2 * S * d \
        * m["moe_intermediate_size"] * 3
    return dense.attention_flops(m, S) + router + routed


def field_eval_flops(m: dict, S: int) -> float:
    """One group: its dense layers and its moe layer, top-k experts a
    token."""
    return sum(_layer_flops(k, m, S)
               for k in _pattern(m["decoder_sparse_step"]))


def tail_flops(m: dict, S: int) -> float:
    pattern = _pattern(m["decoder_sparse_step"])
    n = _counts(m["num_hidden_layers"], m["decoder_sparse_step"])[1]
    return sum(_layer_flops(pattern[i], m, S) for i in range(n))

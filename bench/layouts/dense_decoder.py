"""The dense decoder: every layer the published pre-norm decoder layer,
one layer to a group, no tail layers.

    h <- h + Wo attn(RoPE(q), RoPE(k), v)      q, k, v from RMSNorm(h)
    h <- h + Wd (silu(Wg x) * (Wi x))          x = RMSNorm(h)

Grouped-query attention with RoPE (half split) and optional qk-norm,
causal softmax; SwiGLU feed-forward. The program's ``dense`` block kind
(``models/lm.py``), stacked on a leading depth axis as ``groups/b0``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import HI, _linear, _rmsnorm, _rope

# configuration key -> the program's ArchConfig field, beside the keys
# every layout shares (``spec.SHARED_KEYS``)
ARCH_KEYS = {
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv",
    "head_dim": "d_head",
    "intermediate_size": "d_ff",
    "hidden_act": "act",
    "rope_theta": "rope_theta",
    "qk_norm": "qk_norm",
}


def rehearse(sizes: dict) -> dict:
    """Widths cut to a size the CPU runs in seconds; depth, head ratio,
    tying, qk-norm and dtype kept."""
    ratio = sizes["num_attention_heads"] // sizes["num_key_value_heads"]
    return dict(sizes, hidden_size=64, num_key_value_heads=2,
                num_attention_heads=2 * ratio, head_dim=16,
                intermediate_size=128, vocab_size=512)


def layer_shapes(cfg, prefix: tuple, lead: tuple):
    """(shape, kind) of one layer's leaves under ``prefix``, each with the
    leading axes ``lead``; kind is "norm" or a fan-in."""
    d, H, KV, hd, F = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.d_head, cfg.d_ff
    out = {
        prefix + ("ln1", "scale"): (lead + (d,), "norm"),
        prefix + ("ln2", "scale"): (lead + (d,), "norm"),
        prefix + ("attn", "wq", "kernel"): (lead + (d, H * hd), d),
        prefix + ("attn", "wk", "kernel"): (lead + (d, KV * hd), d),
        prefix + ("attn", "wv", "kernel"): (lead + (d, KV * hd), d),
        prefix + ("attn", "wo", "kernel"): (lead + (H * hd, d), H * hd),
        prefix + ("ffn", "wi", "kernel"): (lead + (d, F), d),
        prefix + ("ffn", "wg", "kernel"): (lead + (d, F), d),
        prefix + ("ffn", "wd", "kernel"): (lead + (F, d), F),
    }
    if cfg.qk_norm:
        out[prefix + ("attn", "q_norm", "scale")] = (lead + (hd,), "norm")
        out[prefix + ("attn", "k_norm", "scale")] = (lead + (hd,), "norm")
    return out


def shapes(cfg):
    """Leaf path -> (shape, kind) of the whole parameter tree."""
    d, V = cfg.d_model, cfg.vocab
    out = {("embed", "table"): ((V, d), 1.0),
           ("ln_f", "scale"): ((d,), "norm")}
    out.update(layer_shapes(cfg, ("groups", "b0"), (cfg.n_layers,)))
    if not cfg.tie_embeddings:
        out[("head", "kernel")] = ((d, V), d)
    return out


def n_fields(m: dict) -> int:
    return m["num_hidden_layers"]


def attention(a, x, m, precision: str):
    """The attention half's output (before the residual add) on the
    normed stream x (B, S, d)."""
    B, S, _ = x.shape
    H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps = m["rms_norm_eps"]
    q = _linear(x, a["wq"]["kernel"], precision).reshape(B, S, H, hd)
    k = _linear(x, a["wk"]["kernel"], precision).reshape(B, S, KV, hd)
    v = _linear(x, a["wv"]["kernel"], precision).reshape(B, S, KV, hd)
    if m["qk_norm"]:
        q = _rmsnorm(q, a["q_norm"]["scale"], eps)
        k = _rmsnorm(k, a["k_norm"]["scale"], eps)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    q = q.reshape(B, S, KV, H // KV, hd)
    sc = jnp.einsum("bsngh,btnh->bngst", q, k, precision=HI) / math.sqrt(hd)
    causal = np.tril(np.ones((S, S), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    ctx = jnp.einsum("bngst,btnh->bsngh", pr, v, precision=HI)
    return _linear(ctx.reshape(B, S, H * hd), a["wo"]["kernel"], precision)


def swiglu(f, x, precision: str):
    up = jax.nn.silu(_linear(x, f["wg"]["kernel"], precision)) \
        * _linear(x, f["wi"]["kernel"], precision)
    return _linear(up, f["wd"]["kernel"], precision)


def block(p, h, m, precision: str):
    """One decoder layer on h (B, S, d), float32."""
    eps = m["rms_norm_eps"]
    h = h + attention(p["attn"], _rmsnorm(h, p["ln1"]["scale"], eps), m,
                      precision)
    x = _rmsnorm(h, p["ln2"]["scale"], eps)
    return h + swiglu(p["ffn"], x, precision)


def group(w, idx):
    """Group ``idx``'s weights: every leaf of ``groups`` at that depth."""
    return jax.tree_util.tree_map(lambda l: l[idx], w["groups"])


def field(w, h, idx, m, precision: str):
    return block(group(w, idx)["b0"], h, m, precision)


def tail(w, h, m, precision: str):
    return h


def attention_flops(m: dict, S: int) -> float:
    """One layer's attention for one causal sequence of S tokens."""
    H, KV, hd, d = (m["num_attention_heads"], m["num_key_value_heads"],
                    m["head_dim"], m["hidden_size"])
    proj = 2 * S * d * (H * hd + 2 * KV * hd) + 2 * S * H * hd * d
    core = 2 * 2 * H * hd * (S * (S + 1) / 2)
    return proj + core


def ffn_flops(m: dict, S: int) -> float:
    return 2 * S * m["hidden_size"] * m["intermediate_size"] * 3


def field_eval_flops(m: dict, S: int) -> float:
    """One evaluation of the depth field: one decoder layer."""
    return attention_flops(m, S) + ffn_flops(m, S)


def tail_flops(m: dict, S: int) -> float:
    return 0.0

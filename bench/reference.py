"""Plain reference of what a served request computes, and its control.

A served request is a prompt scored by a decoder read as a depth ODE
(the paper's continuous-depth model over a pre-norm residual stack):

    f(s, h) = L * (block_{floor(s L)}(h) - h)          L = number of layers

integrated from s = 0 to 1 in K Euler steps of eps = 1/K, with the
hypersolver correction g (paper Eq. 5) when the solver is ``hyper_euler``:

    h <- h + eps f(s_k, h) + eps^2 g(eps, s_k, h, f(s_k, h))
    g = tanh(h W_h + f W_dh + fourier(s) W_s) W_out

K comes from the server's controller: ``fixed`` gives ``fixed_K``; the
residual controller takes one probe of f at s = 0, estimates the error as
rms(g(1, 0, h0, f(0, h0))), sets K = ceil(err / tol) within the bucket
range and snaps it up to the next bucket. Then the final norm and the
vocabulary projection give the logits. The block is the published dense
decoder layer: RMSNorm, grouped-query attention with RoPE (half split)
and optional qk-norm, causal softmax, RMSNorm, SwiGLU.

Everything here is plain ``jax.numpy`` in float32 at the highest matmul
precision, reads the weights the benchmark made (``weights.py``), and
imports nothing of the program. ``precision="fp8"`` is the control: every
linear layer's operands rounded to float8 e4m3 with a per-tensor scale,
the step below the configuration's bfloat16 that would tempt a later
change.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, precision: str):
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HI)


def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (B, S, n, hd); rotation of the two halves of each head."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(S, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(p, h, m, precision: str):
    """One decoder layer on h (B, S, d), float32."""
    B, S, _ = h.shape
    H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps = m["rms_norm_eps"]
    x = _rmsnorm(h, p["ln1"]["scale"], eps)
    a = p["attn"]
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
    h = h + _linear(ctx.reshape(B, S, H * hd), a["wo"]["kernel"], precision)
    x = _rmsnorm(h, p["ln2"]["scale"], eps)
    f = p["ffn"]
    up = jax.nn.silu(_linear(x, f["wg"]["kernel"], precision)) \
        * _linear(x, f["wi"]["kernel"], precision)
    return h + _linear(up, f["wd"]["kernel"], precision)


def _layer(w, idx):
    return jax.tree_util.tree_map(lambda l: l[idx], w["groups"]["b0"])


def _g(gw, eps, s, h, dz):
    n = (gw["w_s"].shape[0] - 1) // 2
    ang = 2 * jnp.pi * jnp.arange(1, n + 1, dtype=jnp.float32) * s
    feats = jnp.concatenate([jnp.sin(ang), jnp.cos(ang), jnp.reshape(s, (1,))])
    pre = (jnp.matmul(h, gw["w_h"], precision=HI)
           + jnp.matmul(dz, gw["w_dh"], precision=HI)
           + jnp.matmul(feats, gw["w_s"], precision=HI))
    return jnp.matmul(jnp.tanh(pre), gw["w_out"], precision=HI)


@partial(jax.jit, static_argnames=("m", "precision"))
def _field(w, h, idx, *, m, precision):
    m = dict(m)
    return m["num_hidden_layers"] * (block(_layer(w, idx), h, m, precision)
                                     - h)


@partial(jax.jit, static_argnames=("hyper",))
def _euler(h, dz, gw, eps, s, *, hyper):
    out = h + eps * dz
    if hyper:
        out = out + eps * eps * _g(gw, eps, s, h, dz)
    return out


@jax.jit
def _probe_err(gw, h0, dz0):
    corr = _g(gw, 1.0, 0.0, h0, dz0)
    return jnp.sqrt(jnp.mean(corr.reshape(corr.shape[0], -1) ** 2, -1))


@partial(jax.jit, static_argnames=("m", "precision"))
def _readout(w, h, *, m, precision):
    m = dict(m)
    x = _rmsnorm(h, w["ln_f"]["scale"], m["rms_norm_eps"])
    head = w["embed"]["table"].T if m["tie_word_embeddings"] \
        else w["head"]["kernel"]
    return _linear(x, head, precision)


def mesh_length(server: dict, err: np.ndarray) -> np.ndarray:
    """K per request as the server's controller chooses it."""
    buckets = np.asarray(sorted(server["buckets"]))
    if server["controller"] == "fixed":
        return np.full(err.shape, int(server["fixed_K"]))
    K = np.ceil(np.maximum(err, 1e-30) / float(server.get("tol", 1e-2)))
    K = np.clip(K, buckets[0], buckets[-1])
    return buckets[np.minimum(np.searchsorted(buckets, K), len(buckets) - 1)]


def _static(m: dict):
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool, str))))


def logits(w, m: dict, h, precision: str = "highest"):
    """Final norm and vocabulary projection of terminal states h."""
    return _readout(w, h, m=_static(m), precision=precision)


def solve(w, gw, m: dict, server: dict, tokens: np.ndarray,
          precision: str = "highest", block: int = 8):
    """Terminal states (B, S, d) float32 and K per request for ``tokens``
    (B, S); ``logits`` turns a block of them into logits.

    ``m`` is the configuration as run (``spec.model_sizes``), ``server``
    the cell's server settings. Requests go through in blocks of
    ``block`` rows (the last one padded), so every call has one shape."""
    hyper = server["solver"].startswith("hyper_")
    if server["solver"] not in ("euler", "hyper_euler"):
        raise SystemExit(f"bench: no reference for solver "
                         f"{server['solver']!r}")
    if server["controller"] not in ("fixed", "residual", "auto"):
        raise SystemExit(f"bench: no reference for controller "
                         f"{server['controller']!r}")
    if server["controller"] == "auto" and not hyper:
        raise SystemExit("bench: controller 'auto' without g probes with an "
                         "embedded pair, which the reference does not model")
    mk = _static(m)
    L = m["num_hidden_layers"]
    n = len(tokens)
    pad = np.concatenate([tokens, np.repeat(tokens[:1], -n % block, 0)])
    outs, Ks = [], []
    for lo in range(0, len(pad), block):
        h = jnp.take(w["embed"]["table"], jnp.asarray(pad[lo:lo + block]),
                     axis=0).astype(jnp.float32)
        dz0 = _field(w, h, 0, m=mk, precision=precision)
        err = np.zeros(block) if server["controller"] == "fixed" \
            else np.asarray(_probe_err(gw, h, dz0))
        K = mesh_length(server, err)
        rows = [(h, dz0, int(K[0]))] if len(set(K)) == 1 else \
            [(h[b:b + 1], dz0[b:b + 1], int(K[b])) for b in range(block)]
        for hb, dz, Kb in rows:
            for k in range(Kb):
                if k:
                    dz = _field(w, hb, k * L // Kb, m=mk, precision=precision)
                hb = _euler(hb, dz, gw, jnp.float32(1.0 / Kb),
                            jnp.float32(k / Kb), hyper=hyper)
            outs.append(hb)
        Ks.append(K)
    return jnp.concatenate(outs)[:n], np.concatenate(Ks)[:n]

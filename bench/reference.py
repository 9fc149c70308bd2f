"""Plain reference of what a served request computes, and its control.

A served request is a prompt scored by a decoder read as a depth ODE
(the paper's continuous-depth model over a pre-norm residual stack):

    f(s, h) = L * (group_{floor(s L)}(h) - h)          L = number of groups

integrated from s = 0 to 1 in K Euler steps of eps = 1/K, with the
hypersolver correction g (paper Eq. 5) when the solver is ``hyper_euler``:

    h <- h + eps f(s_k, h) + eps^2 g(eps, s_k, h, f(s_k, h))
    g = tanh(h W_h + f W_dh + fourier(s) W_s) W_out

K comes from the server's controller: ``fixed`` gives ``fixed_K``; the
residual controller takes one probe of f at s = 0, estimates the error as
rms(g(1, 0, h0, f(0, h0))), sets K = ceil(err / tol) within the bucket
range and snaps it up to the next bucket. Then the discrete tail layers,
the final norm and the vocabulary projection give the logits.

What a group and the tail compute, and how many groups there are, is the
configuration's layout (``layouts/<layout>.py``, ``spec.load_layout``):
this file holds the depth ODE, g, the controller, the readout and the
primitives the layouts share.

Everything here is plain ``jax.numpy`` in float32 at the highest matmul
precision, reads the weights the benchmark made (``weights.py``), and
imports nothing of the program. ``precision="fp8"`` is the control: every
linear layer's operands rounded to float8 e4m3 with a per-tensor scale,
the step below the configuration's bfloat16 that would tempt a later
change.
"""
from __future__ import annotations

import json
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


def _g(gw, eps, s, h, dz):
    n = (gw["w_s"].shape[0] - 1) // 2
    ang = 2 * jnp.pi * jnp.arange(1, n + 1, dtype=jnp.float32) * s
    feats = jnp.concatenate([jnp.sin(ang), jnp.cos(ang), jnp.reshape(s, (1,))])
    pre = (jnp.matmul(h, gw["w_h"], precision=HI)
           + jnp.matmul(dz, gw["w_dh"], precision=HI)
           + jnp.matmul(feats, gw["w_s"], precision=HI))
    return jnp.matmul(jnp.tanh(pre), gw["w_out"], precision=HI)


@partial(jax.jit, static_argnames=("layout", "m", "precision"))
def _field(w, h, idx, *, layout, m, precision):
    m = json.loads(m)
    return layout.n_fields(m) * (layout.field(w, h, idx, m, precision) - h)


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


@partial(jax.jit, static_argnames=("layout", "m", "precision"))
def _readout(w, h, *, layout, m, precision):
    m = json.loads(m)
    h = layout.tail(w, h, m, precision)
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


def _static(m: dict) -> str:
    """``m`` as a static argument of jit, nested groups and lists kept;
    ``json.loads`` gives it back as it was."""
    return json.dumps(m, sort_keys=True)


def logits(layout, w, m: dict, h, precision: str = "highest"):
    """The layout's tail layers, final norm and vocabulary projection of
    terminal states h."""
    return _readout(w, h, layout=layout, m=_static(m), precision=precision)


def solve(layout, w, gw, m: dict, server: dict, tokens: np.ndarray,
          precision: str = "highest", block: int = 8):
    """Terminal states (B, S, d) float32 and K per request for ``tokens``
    (B, S); ``logits`` turns a block of them into logits.

    ``layout`` is the configuration's layout module (``spec.load_layout``),
    ``m`` the configuration as run (``spec.model_sizes``), ``server``
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
    L = layout.n_fields(m)
    n = len(tokens)
    pad = np.concatenate([tokens, np.repeat(tokens[:1], -n % block, 0)])
    outs, Ks = [], []
    for lo in range(0, len(pad), block):
        h = jnp.take(w["embed"]["table"], jnp.asarray(pad[lo:lo + block]),
                     axis=0).astype(jnp.float32)
        dz0 = _field(w, h, 0, layout=layout, m=mk, precision=precision)
        err = np.zeros(block) if server["controller"] == "fixed" \
            else np.asarray(_probe_err(gw, h, dz0))
        K = mesh_length(server, err)
        rows = [(h, dz0, int(K[0]))] if len(set(K)) == 1 else \
            [(h[b:b + 1], dz0[b:b + 1], int(K[b])) for b in range(block)]
        for hb, dz, Kb in rows:
            for k in range(Kb):
                if k:
                    dz = _field(w, hb, k * L // Kb, layout=layout, m=mk,
                                precision=precision)
                hb = _euler(hb, dz, gw, jnp.float32(1.0 / Kb),
                            jnp.float32(k / Kb), hyper=hyper)
            outs.append(hb)
        Ks.append(K)
    return jnp.concatenate(outs)[:n], np.concatenate(Ks)[:n]

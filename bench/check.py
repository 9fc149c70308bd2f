"""Decide ``correct``: served completions against the plain reference.

After the window, a sample of the completions drawn from the seed is
scored again by ``reference.py`` under the configuration's layout. Three
numbers are compared, each with the limit the cell's file states
(``check.limits``):

    max_logit_gap   widest gap, over the seeded positions kept of each
                    sampled request, by which the reference's logit of
                    the served argmax token lies below the reference's
                    best logit, in units of that position's logit spread
                    (std over the vocabulary)
    logit_rel_err   largest |served - reference| logit over the full
                    rows kept, over the largest |reference| logit there
    k_mismatch      sampled requests whose K or NFE differ from what the
                    reference's controller gives (exact: limit 0)

and ``not_ok``, the requests of the window that ended in another status
than ``ok`` (limit 0). A number above its limit makes the run incorrect.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import reference

NAMES = ("max_logit_gap", "logit_rel_err", "k_mismatch", "not_ok")


@jax.jit
def _scores(ref, pos, served_argmax, served_rows):
    """Per request: widest normalized gap of the served argmax at the
    kept positions, and the relative logit error at the kept rows."""
    ref = jnp.take_along_axis(ref, pos[..., None], 1)       # (B, P, V)
    top = jnp.max(ref, -1)
    spread = jnp.std(ref, -1)
    got = jnp.take_along_axis(ref, served_argmax[..., None], -1)[..., 0]
    gap = jnp.max((top - got) / spread, -1)
    rows = ref[:, :served_rows.shape[1]]
    err = jnp.max(jnp.abs(served_rows - rows), (1, 2)) \
        / jnp.max(jnp.abs(rows), (1, 2))
    return gap, err


def _block_scores(ref, pos, argmax, rows):
    gap, err = _scores(ref, jnp.asarray(pos), jnp.asarray(argmax),
                       jnp.asarray(rows))
    return np.asarray(gap), np.asarray(err)


def score(layout, w, gw, m: dict, server: dict, samples: List[dict],
          block: int = 4, control: bool = False) -> Dict[str, float]:
    """Numbers of the served sample against the reference, and with
    ``control`` also the same numbers for the fp8 reference put in the
    program's place (keys prefixed ``control_``)."""
    tokens = np.stack([s["tokens"] for s in samples])
    h, K = reference.solve(layout, w, gw, m, server, tokens)
    hc = reference.solve(layout, w, gw, m, server, tokens,
                         precision="fp8")[0] if control else None
    # one Euler stage per step, and the residual probe's one evaluation
    # is the first step's stage: NFE = K for both controllers
    out = {"max_logit_gap": 0.0, "logit_rel_err": 0.0,
           "k_mismatch": float(sum(int(s["K"] != k or s["nfe"] != k)
                                   for s, k in zip(samples, K)))}
    if control:
        out.update(control_max_logit_gap=0.0, control_logit_rel_err=0.0)
    for lo in range(0, len(samples), block):
        part = samples[lo:lo + block]
        pos = np.stack([s["pos"] for s in part])
        ref = reference.logits(layout, w, m, h[lo:lo + block])
        gap, err = _block_scores(ref, pos,
                                 np.stack([s["argmax"] for s in part]),
                                 np.stack([s["rows"] for s in part]))
        out["max_logit_gap"] = max(out["max_logit_gap"], float(gap.max()))
        out["logit_rel_err"] = max(out["logit_rel_err"], float(err.max()))
        if control:
            ctl = jnp.take_along_axis(
                reference.logits(layout, w, m, hc[lo:lo + block],
                                 precision="fp8"),
                jnp.asarray(pos)[..., None], 1)
            rows = part[0]["rows"].shape[0]
            gap, err = _block_scores(ref, pos, jnp.argmax(ctl, -1),
                                     ctl[:, :rows])
            out["control_max_logit_gap"] = max(
                out["control_max_logit_gap"], float(gap.max()))
            out["control_logit_rel_err"] = max(
                out["control_logit_rel_err"], float(err.max()))
            del ctl
        del ref
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= float(limits[k]) for k in NAMES)


def lines(numbers: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    return [f"check {k}={numbers[k]!r} limit={float(limits[k])!r}"
            for k in NAMES]

"""Pure-jnp oracles for every Pallas kernel, the conv block and the eval
head (the allclose targets).

These are the *semantics* contracts: kernels must match them on every
shape/dtype the tests sweep.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


# ------------------------------------------------------------- hieavg_agg
def hieavg_agg_ref(w: jnp.ndarray, prev: jnp.ndarray, dmean: jnp.ndarray,
                   mask: jnp.ndarray, coef_present: jnp.ndarray,
                   coef_est: jnp.ndarray, n_obs: jnp.ndarray
                   ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused HieAvg mix + history update on one flat leaf.

    w/prev/dmean: [n, L]; mask/coefs/n_obs: [n].
      agg       = sum_n coef_present*w + coef_est*(prev + dmean)
      new_prev  = m*w + (1-m)*(prev + dmean)
      new_dmean = m*((dmean*n_obs + (w - prev)) / (n_obs+1)) + (1-m)*dmean
    Returns (agg [L], new_prev [n, L], new_dmean [n, L]); all math f32,
    outputs cast back to input dtypes.
    """
    f32 = jnp.float32
    wf, pf, df = w.astype(f32), prev.astype(f32), dmean.astype(f32)
    m = mask.astype(f32)[:, None]
    cp = coef_present.astype(f32)[:, None]
    ce = coef_est.astype(f32)[:, None]
    nb = n_obs.astype(f32)[:, None]
    est = pf + df
    agg = jnp.sum(cp * wf + ce * est, axis=0)
    new_prev = m * wf + (1.0 - m) * est
    new_dmean = m * ((df * nb + (wf - pf)) / (nb + 1.0)) + (1.0 - m) * df
    return (agg.astype(w.dtype), new_prev.astype(prev.dtype),
            new_dmean.astype(dmean.dtype))


# ------------------------------------------------------- conv3x3_bias_relu
def conv3x3_bias_relu_ref(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray
                          ) -> jnp.ndarray:
    """Fused conv block oracle: ``relu(conv3x3_same(x, w) + b)``.

    x: [..., H, W, Cin]; w: [3, 3, Cin, Cout]; b: [Cout].  The im2col
    matmul with (i, j, c)-ordered patch channels — identical layout to
    ``models.cnn._conv3x3_same_im2col`` — with f32 accumulation, output
    cast back to ``x.dtype``.
    """
    f32 = jnp.float32
    h, wd = x.shape[-3], x.shape[-2]
    pad = [(0, 0)] * (x.ndim - 3) + [(1, 1), (1, 1), (0, 0)]
    xp = jnp.pad(x, pad)
    cols = jnp.concatenate([xp[..., i:i + h, j:j + wd, :]
                            for i in range(3) for j in range(3)], axis=-1)
    out = jnp.einsum("...k,ko->...o", cols.astype(f32),
                     w.reshape(-1, w.shape[-1]).astype(f32))
    return jnp.maximum(out + b.astype(f32), 0.0).astype(x.dtype)


# ---------------------------------------------------------------- eval_head
def eval_head_ref(feats: jnp.ndarray, wmat: jnp.ndarray, bias: jnp.ndarray,
                  labels: jnp.ndarray) -> jnp.ndarray:
    """Eval oracle: correct-count of the classifier head.

    feats: [M, F]; wmat: [F, C]; bias: [C]; labels: [M] int.  Returns the
    scalar int32 count of rows where ``argmax(feats @ wmat + bias)``
    (f32 logits, first-max-wins) equals the label.
    """
    f32 = jnp.float32
    logits = feats.astype(f32) @ wmat.astype(f32) + bias.astype(f32)
    pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.sum((pred == labels.astype(jnp.int32)).astype(jnp.int32))


# ----------------------------------------------------------------- coef_agg
def coef_agg_ref(w: jnp.ndarray, coef: jnp.ndarray) -> jnp.ndarray:
    """Coefficient-weighted aggregate oracle: ``Σ_n coef[n] · w[n]``.

    w: [n, L]; coef: [n].  f32 math, f32 output (matching the XLA cold /
    FedAvg reference paths, where f32 coefficients promote the product).
    A zero coefficient makes its slot an exact no-op.
    """
    f32 = jnp.float32
    return jnp.sum(coef.astype(f32)[:, None] * w.astype(f32), axis=0)


def coef_agg_pair_ref(w: jnp.ndarray, aux: jnp.ndarray, ca: jnp.ndarray,
                      cb: jnp.ndarray) -> jnp.ndarray:
    """Pair-form aggregate oracle: ``Σ_n ca[n]·w[n] + cb[n]·aux[n]``.

    The delayed-gradient mix: present devices contribute fresh weights
    (``ca``), missing ones their stale pending update (``cb``·aux).
    """
    f32 = jnp.float32
    return jnp.sum(ca.astype(f32)[:, None] * w.astype(f32)
                   + cb.astype(f32)[:, None] * aux.astype(f32), axis=0)


# --------------------------------------------------------- flash attention
def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True,
                        window: Optional[int] = None,
                        q_offset: int = 0) -> jnp.ndarray:
    """Single-head attention oracle. q: [Sq, D]; k/v: [Skv, D] -> [Sq, D].

    Scale 1/sqrt(D); causal/window masks computed from absolute positions
    (q row i has absolute position q_offset + i).
    """
    sq, d = q.shape
    skv = k.shape[0]
    logits = (q.astype(jnp.float32) @ k.astype(jnp.float32).T
              ) / jnp.sqrt(jnp.float32(d))
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(skv)[None, :]
    ok = jnp.ones((sq, skv), bool)
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    logits = jnp.where(ok, logits, -2.0 ** 30)
    probs = jax.nn.softmax(logits, axis=-1)
    return (probs @ v.astype(jnp.float32)).astype(v.dtype)

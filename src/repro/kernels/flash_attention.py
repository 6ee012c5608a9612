"""Blocked flash attention Pallas kernel (TPU target, VMEM-tiled).

Online-softmax attention over KV blocks: for each q block the kernel sweeps
kv blocks keeping a running (max, sum, accumulator) in VMEM scratch —
softmax(QKᵀ)V without ever materializing the [Sq, Skv] logits in HBM.
Covers full-causal and sliding-window (the serving hot-spot for the 32k /
500k assigned shapes).

Grid: (nq, nk), kv innermost.  Blocks: q [BQ, D], k/v [BK, D] — BQ=BK=256
rows × D≤256 f32 lanes ≈ 0.26 MB per operand block; MXU-aligned (multiples
of 128).  GQA/batch are handled by ``vmap`` in ops.py (prepended grid dims).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import default_interpret

BQ = 256
BK = 256
NEG_INF = -2.0 ** 30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            causal: bool, window: Optional[int], q_offset: int, nk: int,
            scale: float, skv: int):
    qi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)           # [BQ, D]
    k = k_ref[...].astype(jnp.float32)           # [BK, D]
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [BQ,BK]

    qpos = q_offset + qi * BQ + jax.lax.broadcasted_iota(jnp.int32,
                                                         (BQ, BK), 0)
    kpos = ki * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
    ok = kpos < skv                              # mask padded kv rows
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                          # [BQ, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (all NEG_INF): keep exp at 0
    p = jnp.exp(s - m_new)
    p = jnp.where(ok, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "interpret"))
def flash_attention_1h(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                       causal: bool = True, window: Optional[int] = None,
                       q_offset: int = 0, interpret: bool | None = None
                       ) -> jnp.ndarray:
    """Single-head flash attention. q [Sq, D], k/v [Skv, D] -> [Sq, D].

    Sq/Skv are padded to the block sizes; D to 128 lanes.  Semantics =
    ``repro.kernels.ref.flash_attention_ref``.  ``interpret=None``
    auto-detects the backend (compiled on TPU, interpreter on CPU).
    """
    if interpret is None:
        interpret = default_interpret()
    sq, d = q.shape
    skv = k.shape[0]
    scale = 1.0 / (d ** 0.5)                      # pre-pad head_dim scale
    pq, pk_, pd = (-sq) % BQ, (-skv) % BK, (-d) % 128
    if pq or pd:
        q = jnp.pad(q, ((0, pq), (0, pd)))
    if pk_ or pd:
        k = jnp.pad(k, ((0, pk_), (0, pd)))
        v = jnp.pad(v, ((0, pk_), (0, pd)))
    nq, nk = q.shape[0] // BQ, k.shape[0] // BK
    dp = q.shape[1]

    kernel = functools.partial(
        _kernel, causal=causal, window=window, q_offset=q_offset, nk=nk,
        scale=scale, skv=skv)

    out = pl.pallas_call(
        kernel,
        grid=(nq, nk),
        in_specs=[
            pl.BlockSpec((BQ, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((BK, dp), lambda i, j: (j, 0)),
            pl.BlockSpec((BK, dp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((BQ, dp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q.shape[0], dp), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((BQ, dp), jnp.float32),    # acc
            pltpu.VMEM((BQ, 1), jnp.float32),     # running max
            pltpu.VMEM((BQ, 1), jnp.float32),     # running sum
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:sq, :d]

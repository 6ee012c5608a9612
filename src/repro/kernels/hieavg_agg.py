"""Fused HieAvg aggregation Pallas kernel (TPU target, VMEM-tiled).

The aggregation step is the paper's compute hot-spot at framework scale:
HieAvg touches every parameter of every client several times per round
(estimate stragglers, weighted mix, history update) — a pure HBM-bandwidth
problem.  XLA emits ~7 separate elementwise passes over the [n, L] stacked
weights; this kernel fuses mask-select, decay-scaled estimation
``γ(w_prev + Δ̄)``, the weighted mean across participants, and the history
update (new ``w_prev``, running ``Δ̄``) into ONE pass over HBM.

Tiling: grid over the flat parameter axis; each program instance holds an
``[n, TILE]`` block of the three [n, L] operands in VMEM (n ≤ 32 clients,
TILE = 2048 f32 lanes → ≤ 0.8 MB/operand·block, comfortably inside the
~16 MB VMEM budget) and writes the aggregate tile plus both history tiles.
The per-participant coefficients (mask, γ-decay, 1/J weights) are tiny [n]
vectors computed outside and broadcast in VMEM.

Batched callers (the engine's ``[N, J, ...]`` dense layout, the sweep
fabric's stacked ``[P]`` point axis) ``vmap`` this kernel — Pallas
prepends the mapped axes as grid dimensions (see
``ops.fused_edge_aggregate_batched``).  Backend selection (compiled vs
interpreter vs the XLA reference path) lives in ``kernels.dispatch``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import default_interpret, out_struct

TILE = 2048


def _kernel(w_ref, prev_ref, dmean_ref, vec_ref,
            agg_ref, nprev_ref, ndmean_ref):
    """One [n, TILE] block.  vec_ref: [4, n] f32 = (mask, coef_present,
    coef_est, n_obs)."""
    f32 = jnp.float32
    w = w_ref[...].astype(f32)          # [n, T]
    prev = prev_ref[...].astype(f32)
    dmean = dmean_ref[...].astype(f32)
    m = vec_ref[0, :][:, None]          # [n, 1]
    cp = vec_ref[1, :][:, None]
    ce = vec_ref[2, :][:, None]
    nb = vec_ref[3, :][:, None]

    est = prev + dmean
    agg_ref[...] = jnp.sum(cp * w + ce * est, axis=0,
                           keepdims=True).astype(agg_ref.dtype)
    nprev_ref[...] = (m * w + (1.0 - m) * est).astype(nprev_ref.dtype)
    new_mean = (dmean * nb + (w - prev)) / (nb + 1.0)
    ndmean_ref[...] = (m * new_mean + (1.0 - m) * dmean
                       ).astype(ndmean_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hieavg_agg(w: jnp.ndarray, prev: jnp.ndarray, dmean: jnp.ndarray,
               mask: jnp.ndarray, coef_present: jnp.ndarray,
               coef_est: jnp.ndarray, n_obs: jnp.ndarray,
               interpret: bool | None = None):
    """Fused aggregate + history update on one flat [n, L] leaf.

    Returns (agg [L], new_prev [n, L], new_dmean [n, L]).  Semantics =
    ``repro.kernels.ref.hieavg_agg_ref``.  ``interpret=None`` auto-detects
    the backend (``dispatch.default_interpret``): compiled ``pallas_call``
    on TPU, interpreter on CPU.  History leaves (``prev``/``dmean``)
    may carry a narrower storage dtype than ``w`` (the engine's
    ``history_dtype`` knob) — math is f32, each output casts back to its
    own operand's dtype.
    """
    if interpret is None:
        interpret = default_interpret()
    n, l = w.shape
    pad = (-l) % TILE
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
        prev = jnp.pad(prev, ((0, 0), (0, pad)))
        dmean = jnp.pad(dmean, ((0, 0), (0, pad)))
    lp = l + pad
    vec = jnp.stack([mask.astype(jnp.float32),
                     coef_present.astype(jnp.float32),
                     coef_est.astype(jnp.float32),
                     n_obs.astype(jnp.float32)])           # [4, n]

    grid = (lp // TILE,)
    ops = (w, prev, dmean, vec)
    agg, nprev, ndmean = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, TILE), lambda i: (0, i)),
            pl.BlockSpec((n, TILE), lambda i: (0, i)),
            pl.BlockSpec((n, TILE), lambda i: (0, i)),
            pl.BlockSpec((4, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, TILE), lambda i: (0, i)),
            pl.BlockSpec((n, TILE), lambda i: (0, i)),
            pl.BlockSpec((n, TILE), lambda i: (0, i)),
        ],
        out_shape=[
            out_struct((1, lp), w.dtype, *ops),
            out_struct((n, lp), prev.dtype, *ops),
            out_struct((n, lp), dmean.dtype, *ops),
        ],
        interpret=interpret,
    )(*ops)
    return agg[0, :l], nprev[:, :l], ndmean[:, :l]

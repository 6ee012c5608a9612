"""Fused coefficient-weighted aggregate Pallas kernel (cold boot + baselines).

The cold-boot rounds and the non-HieAvg baseline aggregators were the
last round phases still paying XLA round trips over the ``[n, L]``
stacked weights: the cold-start mean (``hieavg.*_aggregate_cold``),
FedAvg, and the delayed-gradient mix are all instances of ONE scheme —
a coefficient-weighted sum over the participant axis:

    agg = Σ_n  ca[n] · w[n]                      (single-operand form)
    agg = Σ_n  ca[n] · w[n] + cb[n] · aux[n]     (pair form)

The pair form covers delayed-gradient aggregation, where a missing
device contributes its stale *pending* update (``aux``) instead of a
fresh one.  The tiny [n] coefficient vectors (validity normalization,
staleness discounts) are computed in XLA outside; the kernel does the
heavy [n, L] weighted reduction in one HBM pass per leaf, identical
tiling to ``hieavg_agg`` (grid over the flat parameter axis, [n, TILE]
blocks in VMEM).  ``fused_coef_aggregate`` and
``fused_coef_aggregate_pair`` apply it per leaf of a stacked pytree.

Zero-coefficient padded slots — sweep-fabric padding, invalid devices,
all-miss cold rounds — contribute ``0 · w = 0`` exactly, so padding
stays a numeric no-op and a vmapped batch of edges (Pallas prepends the
``[P, N]`` axes as grid dims) needs no masking inside the kernel.

Outputs are f32 regardless of operand dtype, matching the XLA reference
paths (f32 coefficients promote the product; ``history_dtype=bf16``
runs still aggregate in f32).  Oracles: ``ref.coef_agg_ref`` /
``ref.coef_agg_pair_ref``.  Backend selection + the coefficient recipes
for each aggregator live in ``kernels.dispatch``.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import default_interpret, out_struct

PyTree = Any

TILE = 2048


def _kernel1(w_ref, c_ref, agg_ref):
    """One [n, TILE] block: agg = Σ_n c[n] · w[n]."""
    w = w_ref[...].astype(jnp.float32)
    c = c_ref[0, :][:, None]                     # [n, 1]
    agg_ref[...] = jnp.sum(c * w, axis=0, keepdims=True)


def _kernel2(w_ref, aux_ref, c_ref, agg_ref):
    """One [n, TILE] block: agg = Σ_n ca[n] · w[n] + cb[n] · aux[n]."""
    f32 = jnp.float32
    w = w_ref[...].astype(f32)
    aux = aux_ref[...].astype(f32)
    ca = c_ref[0, :][:, None]
    cb = c_ref[1, :][:, None]
    agg_ref[...] = jnp.sum(ca * w + cb * aux, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def coef_agg(w: jnp.ndarray, coef: jnp.ndarray,
             interpret: bool | None = None) -> jnp.ndarray:
    """Fused ``Σ_n coef[n] · w[n]`` on one flat [n, L] leaf → f32 [L]."""
    if interpret is None:
        interpret = default_interpret()
    n, l = w.shape
    pad = (-l) % TILE
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    lp = l + pad
    cvec = coef.astype(jnp.float32)[None, :]                 # [1, n]
    agg = pl.pallas_call(
        _kernel1,
        grid=(lp // TILE,),
        in_specs=[
            pl.BlockSpec((n, TILE), lambda i: (0, i)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, TILE), lambda i: (0, i)),
        out_shape=out_struct((1, lp), jnp.float32, w, cvec),
        interpret=interpret,
    )(w, cvec)
    return agg[0, :l]


@functools.partial(jax.jit, static_argnames=("interpret",))
def coef_agg_pair(w: jnp.ndarray, aux: jnp.ndarray, ca: jnp.ndarray,
                  cb: jnp.ndarray, interpret: bool | None = None
                  ) -> jnp.ndarray:
    """Fused ``Σ_n ca[n]·w[n] + cb[n]·aux[n]`` on flat [n, L] → f32 [L]."""
    if interpret is None:
        interpret = default_interpret()
    n, l = w.shape
    pad = (-l) % TILE
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
        aux = jnp.pad(aux, ((0, 0), (0, pad)))
    lp = l + pad
    cvec = jnp.stack([ca.astype(jnp.float32), cb.astype(jnp.float32)])
    agg = pl.pallas_call(
        _kernel2,
        grid=(lp // TILE,),
        in_specs=[
            pl.BlockSpec((n, TILE), lambda i: (0, i)),
            pl.BlockSpec((n, TILE), lambda i: (0, i)),
            pl.BlockSpec((2, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, TILE), lambda i: (0, i)),
        out_shape=out_struct((1, lp), jnp.float32, w, aux, cvec),
        interpret=interpret,
    )(w, aux, cvec)
    return agg[0, :l]


def fused_coef_aggregate(stacked_w: PyTree, coef: jnp.ndarray, *,
                         interpret: Optional[bool] = None) -> PyTree:
    """``Σ_n coef[n] · w[n]`` per leaf in one fused pass (f32 outputs).

    The shared core of the cold-boot means and FedAvg: the caller bakes
    every normalization into ``coef`` (see ``dispatch``), so zero-coef
    padded slots are exact no-ops.  Leaves ``[n, ...]`` → ``[...]``.
    """
    def one(w):
        n = w.shape[0]
        return coef_agg(w.reshape(n, -1), coef,
                        interpret=interpret).reshape(w.shape[1:])

    return jax.tree.map(one, stacked_w)


def fused_coef_aggregate_pair(stacked_w: PyTree, aux: PyTree,
                              ca: jnp.ndarray, cb: jnp.ndarray, *,
                              interpret: Optional[bool] = None) -> PyTree:
    """``Σ_n ca[n]·w[n] + cb[n]·aux[n]`` per leaf (delayed-grad mix)."""
    def one(w, a):
        n = w.shape[0]
        return coef_agg_pair(w.reshape(n, -1), a.reshape(n, -1), ca, cb,
                             interpret=interpret).reshape(w.shape[1:])

    return jax.tree.map(one, stacked_w, aux)

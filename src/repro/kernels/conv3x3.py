"""Fused 3x3 im2col conv Pallas kernel (matmul + bias + ReLU epilogue).

The im2col conv is the single hottest op in the CNN train step
(EXPERIMENTS.md §Perf): every masked-SGD scan iteration issues one
``[B·H·W, 9·Cin] × [9·Cin, Cout]`` matmul per conv layer, then bounces
back through XLA for the bias add and the ReLU.  This kernel fuses the
epilogue into the matmul tile: each program instance holds one
``[TILE_M, 9·Cin]`` block of the im2col patches in VMEM, contracts it
against the full (small) weight matrix on the MXU with f32 accumulation,
and applies bias + ReLU before the tile ever leaves VMEM.

The im2col patch construction itself (pad + 9 shifted slices,
``models.cnn.im2col3x3``) stays in XLA on purpose: it is a pure
data-movement op whose transpose is col2im (nine shifted adds, its own
custom VJP), so dx comes from there while the custom VJP below covers
only the matmul + bias + ReLU core:

  forward   y  = relu(cols @ W + b)
  backward  dz = dy * (y > 0)
            dcols = dz @ Wᵀ          (per tile, fused)
            dW    = colsᵀ @ dz       (per-tile partials, summed in XLA)
            db    = Σ dz

Both backward matmuls run in the same tiled pass.  The per-tile dW/db
partials land in a small ``[num_tiles, ...]`` scratch output and are
reduced outside the kernel — no cross-program accumulation, so the
kernel stays correct under ``vmap`` (the engine's stacked device axis
and the sweep fabric's ``[P]`` point axis are prepended as grid
dimensions by Pallas batching).

Padding: M is padded to a TILE_M multiple with zero rows.  Forward pad
rows compute ``relu(b)`` and are sliced off; backward pad rows carry
``dy = 0`` so ``dz = 0`` and they contribute exactly nothing to dW/db.

Oracle: ``ref.conv3x3_bias_relu_ref``.  Backend selection lives in
``kernels.dispatch.conv3x3_bias_relu``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.models.cnn import im2col3x3

from .dispatch import default_interpret, out_struct

#: Rows of the im2col matrix per program instance.  At the paper's DEFAULT
#: geometry (K = 9·32 = 288, Cout = 64) one block is 256·288·4 ≈ 0.3 MB —
#: well inside VMEM next to the full weight matrix (288·64·4 ≈ 74 kB).
TILE_M = 256


def _fwd_kernel(cols_ref, w_ref, b_ref, out_ref):
    """One [TILE_M, K] block: relu(cols @ W + b), f32 accumulation."""
    f32 = jnp.float32
    acc = jnp.dot(cols_ref[...].astype(f32), w_ref[...].astype(f32),
                  preferred_element_type=f32)
    acc = acc + b_ref[...].astype(f32)          # b_ref [1, N]
    out_ref[...] = jnp.maximum(acc, 0.0).astype(out_ref.dtype)


def _bwd_kernel(cols_ref, w_ref, y_ref, dy_ref,
                dcols_ref, dw_ref, db_ref):
    """Backward tile: relu grad + both matmuls.  dw/db are per-tile
    partials written to [1, K, N] / [1, 1, N] blocks (summed outside)."""
    f32 = jnp.float32
    dz = dy_ref[...].astype(f32) * (y_ref[...].astype(f32) > 0.0)
    w = w_ref[...].astype(f32)
    dcols_ref[...] = jnp.dot(dz, w.T,
                             preferred_element_type=f32
                             ).astype(dcols_ref.dtype)
    dw_ref[...] = jnp.dot(cols_ref[...].astype(f32).T, dz,
                          preferred_element_type=f32)[None]
    db_ref[...] = jnp.sum(dz, axis=0)[None, None]


def _pad_m(a: jnp.ndarray, pad: int) -> jnp.ndarray:
    return jnp.pad(a, ((0, pad), (0, 0))) if pad else a


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fwd_call(interpret: bool, cols: jnp.ndarray, wmat: jnp.ndarray,
              bias: jnp.ndarray) -> jnp.ndarray:
    m, k = cols.shape
    n = wmat.shape[1]
    pad = (-m) % TILE_M
    mp = m + pad
    ops = (_pad_m(cols, pad), wmat, bias[None, :])
    y = pl.pallas_call(
        _fwd_kernel,
        grid=(mp // TILE_M,),
        in_specs=[
            pl.BlockSpec((TILE_M, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_M, n), lambda i: (i, 0)),
        out_shape=out_struct((mp, n), cols.dtype, *ops),
        interpret=interpret,
    )(*ops)
    return y[:m]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd_call(interpret: bool, cols: jnp.ndarray, wmat: jnp.ndarray,
              y: jnp.ndarray, dy: jnp.ndarray):
    m, k = cols.shape
    n = wmat.shape[1]
    pad = (-m) % TILE_M
    mp = m + pad
    nt = mp // TILE_M
    ops = (_pad_m(cols, pad), wmat, _pad_m(y, pad), _pad_m(dy, pad))
    dcols, dw_part, db_part = pl.pallas_call(
        _bwd_kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((TILE_M, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((TILE_M, n), lambda i: (i, 0)),
            pl.BlockSpec((TILE_M, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE_M, k), lambda i: (i, 0)),
            pl.BlockSpec((1, k, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            out_struct((mp, k), cols.dtype, *ops),
            out_struct((nt, k, n), jnp.float32, *ops),
            out_struct((nt, 1, n), jnp.float32, *ops),
        ],
        interpret=interpret,
    )(*ops)
    dw = jnp.sum(dw_part, axis=0).astype(wmat.dtype)
    db = jnp.sum(db_part, axis=0)[0].astype(wmat.dtype)
    return dcols[:m], dw, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _matmul_bias_relu(interpret: bool, cols: jnp.ndarray,
                      wmat: jnp.ndarray, bias: jnp.ndarray) -> jnp.ndarray:
    """relu(cols @ wmat + bias) with both passes fused in Pallas."""
    return _fwd_call(interpret, cols, wmat, bias)


def _mbr_fwd(interpret, cols, wmat, bias):
    y = _fwd_call(interpret, cols, wmat, bias)
    return y, (cols, wmat, y)


def _mbr_bwd(interpret, res, dy):
    cols, wmat, y = res
    return _bwd_call(interpret, cols, wmat, y, dy)


_matmul_bias_relu.defvjp(_mbr_fwd, _mbr_bwd)


def conv3x3_bias_relu(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Fused ``relu(conv3x3_same(x, w) + b)`` — the CNN conv block.

    x: [..., H, W, Cin]; w: [3, 3, Cin, Cout]; b: [Cout].  Semantics =
    ``ref.conv3x3_bias_relu_ref`` (im2col matmul with f32 accumulation,
    outputs cast back to ``x.dtype``).  Differentiable in x/w/b via the
    fused backward kernel; ``interpret=None`` auto-detects the backend
    (``dispatch.default_interpret``).
    """
    if interpret is None:
        interpret = default_interpret()
    cin, cout = w.shape[2], w.shape[3]
    cols = im2col3x3(x)
    y = _matmul_bias_relu(bool(interpret), cols.reshape(-1, 9 * cin),
                          w.reshape(9 * cin, cout), b)
    return y.reshape(x.shape[:-1] + (cout,))

"""Fused eval-head Pallas kernel: logits → argmax → correct-count.

The engine's post-scan eval counts correct predictions
(``cnn_correct_fast``) for every global round: a dense matmul for the
logits, an argmax, an equality compare and a sum — three extra XLA passes over a ``[M, C]`` logits buffer that is
never needed again.  This kernel folds the whole chain into the matmul
tile: each program instance contracts a ``[tile_m, F]`` block of pooled
features against the full classifier matrix, takes the row argmax and
compares against the labels without the logits ever leaving VMEM.  Per
tile it emits its int32 correct-count broadcast over one ``[8, 128]``
block (a TPU output block's last two dims must be multiples of 8 and 128
or span the array), and the tiny ``[num_tiles, 8, 128]`` partials are
reduced in XLA — no cross-program accumulation, so the kernel stays
correct under ``vmap`` (sweep ``[P]`` axes prepend as grid dims).

VMEM: the classifier is held transposed, ``[C, F]``, so its few class
rows pad to 8-row sublane tiles rather than its few columns to 128-lane
tiles (at the paper's widths, F = 12544 and C = 10: 0.8 MB instead of
6.4 MB per buffer).  The row tile shrinks with F so a double-buffered
feature block stays near ``_FEATS_BLOCK_BYTES``.  Without both, the
vmapped kernel of a sweep needs 24.5 MB of a v5e's 16 MB scoped VMEM at
those widths.

Padding: M is padded to a tile multiple with zero feature rows and
``label = -1`` — argmax is always ≥ 0, so padded rows can never count as
correct (an exact no-op, matching the kernel plane's padded-slot
contract).

Oracle: ``ref.eval_head_ref``.  Backend selection lives in
``kernels.dispatch.eval_head``; ``models.cnn.cnn_correct_fast`` returns
the count, and the engine divides the sum of its chunks' counts by the
true row count to return an accuracy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import default_interpret, out_struct

#: Most rows per program instance (narrow feature widths).
TILE_M = 256
#: Target bytes of one f32 feature block (it is double-buffered).
_FEATS_BLOCK_BYTES = 4 << 20
#: One aligned int32 output block per program: the count, broadcast.
_OUT_TILE = (8, 128)


def _tile_m(f: int) -> int:
    """Rows per program: ``TILE_M``, cut so a ``[rows, f]`` f32 block
    stays near ``_FEATS_BLOCK_BYTES``; a multiple of 8 (sublane tile)."""
    return max(8, min(TILE_M, _FEATS_BLOCK_BYTES // (4 * f) // 8 * 8))


def _kernel(f_ref, wt_ref, b_ref, y_ref, out_ref):
    """One [tile_m, F] block: correct-count of argmax(f @ W + b) vs y,
    with W held transposed (``wt_ref`` is [C, F])."""
    f32 = jnp.float32
    logits = jax.lax.dot_general(
        f_ref[...].astype(f32), wt_ref[...].astype(f32),
        (((1,), (1,)), ((), ())), preferred_element_type=f32) \
        + b_ref[...].astype(f32)
    pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # [tile_m]
    hit = (pred == y_ref[...][:, 0]).astype(jnp.int32)
    out_ref[...] = jnp.full(out_ref.shape, jnp.sum(hit), jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def eval_head(feats: jnp.ndarray, wmat: jnp.ndarray, bias: jnp.ndarray,
              labels: jnp.ndarray, interpret: bool | None = None
              ) -> jnp.ndarray:
    """Correct-prediction count of the classifier head in one fused pass.

    feats: [M, F]; wmat: [F, C]; bias: [C]; labels: [M] int.  Returns a
    scalar int32 count of rows where ``argmax(feats @ wmat + bias) ==
    labels``.  Semantics = ``ref.eval_head_ref`` (f32 logits math, first-
    max-wins argmax).  ``interpret=None`` auto-detects the backend.
    """
    if interpret is None:
        interpret = default_interpret()
    m, f = feats.shape
    c = wmat.shape[1]
    tm = _tile_m(f)
    pad = (-m) % tm
    mp = m + pad
    nt = mp // tm
    if pad:
        feats = jnp.pad(feats, ((0, pad), (0, 0)))
    lab = jnp.full((mp, 1), -1, jnp.int32)
    lab = lab.at[:m, 0].set(labels.astype(jnp.int32))
    ops = (feats, wmat.T, bias[None, :], lab)
    counts = pl.pallas_call(
        _kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((tm, f), lambda i: (i, 0)),
            pl.BlockSpec((c, f), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((tm, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1,) + _OUT_TILE, lambda i: (i, 0, 0)),
        out_shape=out_struct((nt,) + _OUT_TILE, jnp.int32, *ops),
        interpret=interpret,
    )(*ops)
    return jnp.sum(counts[:, 0, 0])

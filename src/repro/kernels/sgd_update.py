"""Fused masked-SGD update Pallas kernel (TPU target, VMEM-tiled).

The engine's train-step inner loop applies ``w ← w − (lr·ok)·g`` to every
parameter of every device, every SGD step of every edge round — after the
HieAvg aggregation this is the second HBM-bandwidth hot-spot of a run.
The lr scale and the sweep fabric's padded-step mask are folded into ONE
scalar by the caller (``ok`` ∈ {0, 1}, so a padded step is an exact
identity), and the kernel does the whole read-modify-write in a single
pass over each ``[n, L]`` leaf: read w and g once, write w′ once.

Tiling mirrors ``hieavg_agg``: grid over the flat parameter axis, each
program instance holds an ``[n, TILE]`` block of w and g in VMEM
(n = stacked devices ≤ ~32, TILE = 2048 f32 lanes) plus the broadcast
``[1, 1]`` scale; math in f32, outputs cast back to the storage dtype.

Semantics contract: ``repro.kernels.ref.sgd_update_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import default_interpret, out_struct

TILE = 2048


def _kernel(w_ref, g_ref, s_ref, out_ref):
    """One [n, TILE] block: out = w - s*g, f32 math."""
    f32 = jnp.float32
    s = s_ref[0, 0]
    out_ref[...] = (w_ref[...].astype(f32)
                    - s * g_ref[...].astype(f32)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sgd_update(w: jnp.ndarray, g: jnp.ndarray, scale: jnp.ndarray,
               interpret: bool | None = None) -> jnp.ndarray:
    """Fused SGD update on one flat [n, L] leaf: ``w - scale * g``.

    ``scale`` is a (possibly traced) scalar — lr × step-validity, so 0
    makes the update an exact identity.  ``interpret=None`` auto-detects
    the backend (compiled on TPU, interpreter on CPU).  Semantics =
    ``repro.kernels.ref.sgd_update_ref``.
    """
    if interpret is None:
        interpret = default_interpret()
    n, l = w.shape
    pad = (-l) % TILE
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
        g = jnp.pad(g, ((0, 0), (0, pad)))
    lp = l + pad
    s = jnp.asarray(scale, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        _kernel,
        grid=(lp // TILE,),
        in_specs=[
            pl.BlockSpec((n, TILE), lambda i: (0, i)),
            pl.BlockSpec((n, TILE), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n, TILE), lambda i: (0, i)),
        out_shape=out_struct((n, lp), w.dtype, w, g, s),
        interpret=interpret,
    )(w, g, s)
    return out[:, :l]

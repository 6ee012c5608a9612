"""Public jit'd wrappers around the Pallas kernels.

The HieAvg entry points mirror ``repro.core.hieavg`` semantics on stacked
pytrees, dispatching each leaf (flattened to ``[n, L]``) through the fused
``hieavg_agg`` kernel — one HBM pass per leaf instead of XLA's ~7:

  * ``fused_mix_and_update`` — the kernel analogue of
    ``hieavg._mix_and_update`` (eq. 4/5): traced ``part_weights`` /
    ``gamma0`` / ``lam``, composes under ``vmap``/``scan`` inside the
    engine's compiled program.
  * ``fused_edge_aggregate_batched`` — the engine's dense layer API
    (eq. 4 for all N edges at once): ``[N, J, ...]`` stacked leaves, a
    ``valid`` mask whose padded slots carry zero part weight (numeric
    no-ops, exactly like ``hieavg.edge_aggregate_batched``), the kernel
    vmapped over the edge axis (Pallas prepends it — and the sweep
    fabric's stacked ``[P]`` point axis above it — as grid dimensions).
  * ``fused_edge_aggregate`` — the original single-edge API (eq. 4,
    static ``gamma0``/``lam``), kept for direct callers and benchmarks.

``fused_sgd_update`` is the train-step inner loop: the masked SGD update
``w − (lr·ok)·g`` in one pass per leaf (``kernels.sgd_update``).

``eval_head`` (re-exported from its kernel module) and the
``fused_coef_aggregate`` pair close the rest of the round: the
classifier-head correct-count eval, and the generalized coefficient
aggregate shared by the cold-boot means, FedAvg and the delayed-gradient
mix (zero-coefficient padded slots stay exact no-ops).  The CNN conv
block has no kernel: ``dispatch.conv3x3_bias_relu`` runs XLA's own
convolution.

``flash_attention`` is the multi-head GQA front-end of the single-head
kernel: batch, kv-head and group dims are vmapped (Pallas prepends them as
grid dimensions).

Every wrapper takes ``interpret=None`` = backend auto-detection
(``dispatch.default_interpret``): compiled ``pallas_call`` on TPU,
interpreter on CPU.  The engine does not call these directly — it goes
through ``kernels.dispatch`` so ``kernel_mode="xla"``/``"auto"`` can route
to the pure-XLA reference path instead.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.hieavg import History
from .coef_agg import coef_agg, coef_agg_pair
from .dispatch import default_interpret
from .eval_head import eval_head
from .flash_attention import flash_attention_1h
from .hieavg_agg import hieavg_agg
from .sgd_update import sgd_update

PyTree = Any


# ----------------------------------------------------------------- hieavg
def fused_mix_and_update(stacked_w: PyTree, mask: jnp.ndarray,
                         history: History, part_weights: jnp.ndarray,
                         gamma0, lam, normalize: bool = False, *,
                         interpret: Optional[bool] = None
                         ) -> tuple[PyTree, History]:
    """Kernel-fused ``hieavg._mix_and_update`` (eq. 4/5) on [n, ...] leaves.

    ``part_weights``/``gamma0``/``lam`` may be traced (the engine sweeps
    decay factors as data) — the tiny per-participant coefficient vectors
    are computed in XLA and broadcast into the kernel, which does the
    heavy [n, L] mix + history update in one HBM pass per leaf.  An
    all-zero ``part_weights`` row (sweep-fabric padding) contributes
    exactly nothing.  Returns (aggregate, updated History) — allclose to
    the core path; no jit boundary, composes under vmap/scan.
    """
    if interpret is None:
        interpret = default_interpret()
    m = mask.astype(jnp.float32)
    gamma = gamma0 * lam ** (history.miss_count + 1.0)    # k' >= 1
    coef = part_weights * (m + (1.0 - m) * gamma)
    if normalize:
        coef = coef / jnp.maximum(jnp.sum(coef), 1e-12)
    coef_present = coef * m
    coef_est = coef * (1.0 - m)
    n = mask.shape[0]

    leaves_w, treedef = jax.tree_util.tree_flatten(stacked_w)
    leaves_p = treedef.flatten_up_to(history.prev_w)
    leaves_d = treedef.flatten_up_to(history.delta_mean)

    aggs, nprevs, ndmeans = [], [], []
    for w, p, d in zip(leaves_w, leaves_p, leaves_d):
        flat = (n, -1)
        a, np_, nd = hieavg_agg(w.reshape(flat), p.reshape(flat),
                                d.reshape(flat), mask, coef_present,
                                coef_est, history.n_obs,
                                interpret=interpret)
        aggs.append(a.reshape(w.shape[1:]))
        nprevs.append(np_.reshape(p.shape))
        ndmeans.append(nd.reshape(d.shape))

    new_hist = History(
        prev_w=jax.tree_util.tree_unflatten(treedef, nprevs),
        delta_mean=jax.tree_util.tree_unflatten(treedef, ndmeans),
        n_obs=history.n_obs + m,
        miss_count=(history.miss_count + 1.0) * (1.0 - m),
    )
    return jax.tree_util.tree_unflatten(treedef, aggs), new_hist


def fused_edge_aggregate_batched(stacked_w: PyTree, mask: jnp.ndarray,
                                 history: History, valid: jnp.ndarray,
                                 gamma0, lam, normalize: bool = False, *,
                                 interpret: Optional[bool] = None
                                 ) -> tuple[PyTree, History]:
    """Eq. (4) for ALL N edges through the fused kernel in one vmapped call.

    Mirrors ``hieavg.edge_aggregate_batched`` exactly: stacked_w leaves
    ``[N, J, ...]``, mask/valid ``[N, J]``, per-edge part weights
    ``valid / J_e`` (zero on padded slots, so padding stays a numeric
    no-op).  The edge axis is vmapped over the kernel — Pallas prepends it
    (and any sweep-stacked ``[P]`` axis above) as grid dimensions, so one
    ``pallas_call`` per leaf covers the whole dense layout.
    """
    if interpret is None:
        interpret = default_interpret()
    v = valid.astype(jnp.float32)
    pw = v / jnp.maximum(jnp.sum(v, axis=-1, keepdims=True), 1.0)

    def one_edge(w, m, h, p):
        return fused_mix_and_update(w, m, h, p, gamma0, lam, normalize,
                                    interpret=interpret)

    return jax.vmap(one_edge)(stacked_w, mask, history, pw)


@functools.partial(jax.jit, static_argnames=("gamma0", "lam", "normalize",
                                             "interpret"))
def fused_edge_aggregate(stacked_w: PyTree, mask: jnp.ndarray,
                         history: History, *, gamma0: float = 0.9,
                         lam: float = 0.9, normalize: bool = False,
                         interpret: Optional[bool] = None
                         ) -> tuple[PyTree, History]:
    """Kernel-fused equivalent of ``hieavg.edge_aggregate`` (eq. 4).

    The single-edge API (uniform 1/n part weights, static decay factors)
    — direct callers and ``benchmarks/kernel_bench``.  Returns
    (edge model, updated History) — allclose to the core path.
    """
    n = mask.shape[0]
    pw = jnp.full((n,), 1.0 / n, jnp.float32)
    return fused_mix_and_update(stacked_w, mask, history, pw, gamma0, lam,
                                normalize, interpret=interpret)


# --------------------------------------------------------------- coef agg
def fused_coef_aggregate(stacked_w: PyTree, coef: jnp.ndarray, *,
                         interpret: Optional[bool] = None) -> PyTree:
    """``Σ_n coef[n] · w[n]`` per leaf in one fused pass (f32 outputs).

    The shared core of the cold-boot means and FedAvg: the caller bakes
    every normalization into ``coef`` (see ``dispatch``), so zero-coef
    padded slots are exact no-ops.  Leaves ``[n, ...]`` → ``[...]``.
    """
    if interpret is None:
        interpret = default_interpret()

    def one(w):
        n = w.shape[0]
        return coef_agg(w.reshape(n, -1), coef,
                        interpret=interpret).reshape(w.shape[1:])

    return jax.tree.map(one, stacked_w)


def fused_coef_aggregate_pair(stacked_w: PyTree, aux: PyTree,
                              ca: jnp.ndarray, cb: jnp.ndarray, *,
                              interpret: Optional[bool] = None) -> PyTree:
    """``Σ_n ca[n]·w[n] + cb[n]·aux[n]`` per leaf (delayed-grad mix)."""
    if interpret is None:
        interpret = default_interpret()

    def one(w, a):
        n = w.shape[0]
        return coef_agg_pair(w.reshape(n, -1), a.reshape(n, -1), ca, cb,
                             interpret=interpret).reshape(w.shape[1:])

    return jax.tree.map(one, stacked_w, aux)


# -------------------------------------------------------------------- sgd
def fused_sgd_update(params: PyTree, grads: PyTree, scale, *,
                     interpret: Optional[bool] = None) -> PyTree:
    """Masked SGD update ``w − scale·g`` in one fused pass per leaf.

    ``scale`` is the (traced) lr × step-validity scalar — the sweep
    fabric's padded steps pass 0 and the update is an exact identity.
    Leaves carry a leading stacked-device dim ``[D, ...]`` and are
    flattened to ``[D, L]`` for the kernel.  Oracle:
    ``ref.sgd_update_ref``; XLA reference path: the engine's plain
    ``tree.map`` (``dispatch.sgd_update(mode="xla")``).
    """
    if interpret is None:
        interpret = default_interpret()

    def one(w, g):
        n = w.shape[0]
        out = sgd_update(w.reshape(n, -1), g.reshape(n, -1), scale,
                         interpret=interpret)
        return out.reshape(w.shape)

    return jax.tree.map(one, params, grads)


# ------------------------------------------------------------------ flash
@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    q_offset: int = 0, interpret: Optional[bool] = None
                    ) -> jnp.ndarray:
    """GQA flash attention. q [B,Sq,H,Dh]; k/v [B,Skv,Hkv,Dh] -> like q.

    Matches ``repro.models.attention._sdpa`` semantics (scale 1/sqrt(Dh)).
    """
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, dh)

    fn = functools.partial(flash_attention_1h, causal=causal, window=window,
                           q_offset=q_offset, interpret=interpret)
    # [B, Hkv, G] prepended as grid dims by vmap (outermost applied last;
    # each vmap strips the leading mapped axis of the operands it maps)
    fn = jax.vmap(fn, in_axes=(0, None, None))        # G (q only)
    fn = jax.vmap(fn, in_axes=(0, 0, 0))              # Hkv
    fn = jax.vmap(fn, in_axes=(0, 0, 0))              # B
    qb = jnp.moveaxis(qg, 1, -2)                      # [B, Hkv, G, Sq, Dh]
    kb = jnp.moveaxis(k, 1, -2)                       # [B, Hkv, Skv, Dh]
    out = fn(qb, kb, jnp.moveaxis(v, 1, -2))          # [B, Hkv, G, Sq, Dh]
    return jnp.moveaxis(out, -2, 1).reshape(b, sq, h, dh)

"""Kernel-plane backend dispatch — who runs a fused op, and how.

Every compute hot-spot with a Pallas kernel has THREE executable forms:

  * ``pallas``    — the compiled ``pallas_call`` (TPU; fails to lower
                    on CPU, which has no Mosaic backend),
  * ``interpret`` — the same kernel through the Pallas interpreter
                    (jax-level emulation: traceable, jittable, correct
                    everywhere, slower — the CPU validation path),
  * ``xla``       — the pure-jnp reference path (``core.hieavg``'s fused
                    ``_mix_and_update`` tree.map / the plain SGD tree.map),
                    which XLA fuses well on CPU.

This module is the single place that picks between them.  The knob is a
``kernel_mode`` string threaded ``BHFLSimulator``/``run_sweep`` →
``run_engine`` (like ``history_dtype``):

  * ``"auto"``      — ``pallas`` on TPU, ``xla`` on CPU.  The default
                      everywhere: accelerators get the one-HBM-pass fused
                      kernels, CPU keeps the XLA path with zero overhead
                      (never the interpreter loop).
  * ``"pallas"`` / ``"interpret"`` / ``"xla"`` — force a path (tests pin
                      ``interpret`` vs ``xla`` engine parity on CPU).

``default_interpret()`` is the companion policy for DIRECT kernel calls
(``ops.flash_attention``, ``hieavg_agg`` benchmarks): when the caller
passes ``interpret=None`` the kernel compiles on TPU and interprets on
CPU — previously ``interpret=True`` was hard-coded "until the launch layer
flips it off", which nothing ever did, so real hardware silently ran the
interpreter.

Layering: this module imports only jax + ``core.hieavg`` at module level
and pulls the kernel wrappers (``ops``) in lazily, so the kernel modules
may import ``default_interpret`` from here without a cycle.

The dispatch entry points (``edge_aggregate_batched``,
``global_aggregate``, ``sgd_update``) mirror the engine's calling
conventions exactly — batched ``[N, J, ...]`` stacked trees with validity
masks, traced ``gamma0``/``lam`` scalars — and guarantee the same
padded-slot no-op contract as the XLA path (zero part-weight padding
contributes exactly nothing; see docs/ARCHITECTURE.md §Kernel plane).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import baselines, hieavg
from repro.core.hieavg import History

PyTree = Any

#: The engine round phases with a fused kernel, in round order.  Under a
#: fused mode (``pallas``/``interpret``) every phase listed here runs in
#: a Pallas kernel, but the conv, which runs XLA's own convolution fused
#: with its bias and ReLU; under ``xla`` all run the pure-jnp reference
#: paths.
#: (``t_fedavg``/``d_fedavg`` — legacy baselines outside the switched
#: set — and the tiny history-bookkeeping updates stay XLA by design.)
ROUND_PHASES = ("train_conv_fwd_bwd", "sgd_update", "warm_edge_aggregate",
                "warm_global_aggregate", "cold_boot_aggregate",
                "fedavg_aggregate", "delayed_grad_aggregate", "eval_head")


def fused_phase_coverage(mode: str = "auto") -> dict:
    """Which round phases run fused under ``mode`` (resolved) — the
    benchmarks' coverage column (`padded_flop_frac`-style)."""
    fused = resolve_kernel_mode(mode) in ("pallas", "interpret")
    return {phase: fused for phase in ROUND_PHASES}

#: The accepted ``kernel_mode`` values, in resolution order.
KERNEL_MODES = ("auto", "pallas", "interpret", "xla")

#: The backend whose Pallas kernels compile (Mosaic), and the one
#: accelerator this repository runs on.
_COMPILED_BACKEND = "tpu"


def resolve_kernel_mode(mode: str = "auto") -> str:
    """Resolve a ``kernel_mode`` knob to a concrete path.

    ``"auto"`` → ``"pallas"`` when the default jax backend can compile
    Pallas kernels (TPU), else ``"xla"`` — never ``"interpret"``: the
    interpreter is a validation tool, not a production path.  Explicit
    modes pass through; unknown strings raise naming the valid set.
    Callers resolve once (host-side) so jit caches key on the concrete
    mode, not on ``"auto"``.
    """
    if mode not in KERNEL_MODES:
        raise ValueError(
            f"unknown kernel_mode {mode!r}; expected one of {KERNEL_MODES}")
    if mode != "auto":
        return mode
    return "pallas" if jax.default_backend() == _COMPILED_BACKEND else "xla"


def default_interpret() -> bool:
    """Interpret flag for direct kernel calls when the caller didn't pick:
    compile on TPU, interpret on CPU (where Pallas cannot lower)."""
    return jax.default_backend() != _COMPILED_BACKEND


def _interpret(mode: str) -> bool:
    """The ``pallas_call`` interpret flag for a resolved fused mode."""
    return mode == "interpret"


def varying_axes(*trees) -> frozenset:
    """The manual mesh axes that any leaf of ``trees`` varies over under
    ``jax.shard_map`` (empty outside it)."""
    return frozenset().union(*(jax.typeof(x).vma
                               for x in jax.tree.leaves(trees)))


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A ``pallas_call`` output type that varies over the mesh axes its
    operands vary over: ``jax.shard_map``'s type checker needs the kernel's
    outputs to say so, and the sweep fabric runs the kernels inside it."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=varying_axes(*operands))


# --------------------------------------------------------- engine dispatch
def edge_aggregate_batched(stacked_w: PyTree, mask: jnp.ndarray,
                           history: History, valid: jnp.ndarray,
                           gamma0, lam, normalize: bool = False, *,
                           mode: str = "auto") -> tuple[PyTree, History]:
    """Eq. (4) for all N edges — ``hieavg.edge_aggregate_batched``
    semantics, routed through the fused kernel when ``mode`` says so.

    stacked_w leaves ``[N, J, ...]``; mask/valid ``[N, J]``; history
    likewise; ``gamma0``/``lam`` may be traced.  Padded slots
    (``valid`` False) carry zero part weight on every path.
    """
    mode = resolve_kernel_mode(mode)
    if mode == "xla":
        return hieavg.edge_aggregate_batched(stacked_w, mask, history,
                                             valid, gamma0, lam, normalize)
    from . import ops
    return ops.fused_edge_aggregate_batched(
        stacked_w, mask, history, valid, gamma0, lam, normalize,
        interpret=_interpret(mode))


def global_aggregate(stacked_w: PyTree, mask: jnp.ndarray, history: History,
                     part_weights: jnp.ndarray, gamma0, lam,
                     normalize: bool = False, *, mode: str = "auto"
                     ) -> tuple[PyTree, History]:
    """Eq. (5) on the leader — ``hieavg.aggregate`` semantics (traced
    ``part_weights``/``gamma0``/``lam``), fused-kernel routed."""
    mode = resolve_kernel_mode(mode)
    if mode == "xla":
        return hieavg.aggregate(stacked_w, mask, history, part_weights,
                                gamma0, lam, normalize)
    from . import ops
    return ops.fused_mix_and_update(stacked_w, mask, history, part_weights,
                                    gamma0, lam, normalize,
                                    interpret=_interpret(mode))


def sgd_update(params: PyTree, grads: PyTree, scale, *,
               mode: str = "auto") -> PyTree:
    """The train-step inner update ``w - scale * g`` per leaf.

    ``scale`` is the (traced) lr × step-validity product — a padded sweep
    step passes 0 and the update is exact identity on every path.  The
    fused path does the read-modify-write in one pass per ``[D, L]`` leaf
    (oracle: ``ref.sgd_update_ref``); ``xla`` is the engine's original
    ``tree.map``, bit-identical to what ``run_engine`` always did.
    """
    mode = resolve_kernel_mode(mode)
    if mode == "xla":
        return jax.tree.map(lambda w, g: w - scale * g, params, grads)
    from . import ops
    return ops.fused_sgd_update(params, grads, scale,
                                interpret=_interpret(mode))


def conv3x3_bias_relu(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
                      mode: str = "auto", relu: bool = True) -> jnp.ndarray:
    """The CNN conv block ``relu(conv3x3_same(x, w) + b)``; with
    ``relu=False`` the pre-activation ``conv3x3_same(x, w) + b``, for a
    block that pools before its ReLU (``models.cnn._features_fused``).

    x: [..., H, W, Cin]; w: [3, 3, Cin, Cout]; b: [Cout].  The fused
    modes run XLA's own convolution, which XLA fuses with the bias and
    ReLU and differentiates itself: on a TPU it never writes the 9x-wide
    im2col patches to HBM, and under the engine's ``vmap`` over devices
    (and a sweep's over points) the per-device weights become grouped
    convolutions.  Leading dims beyond one batch dim fold into N.  As in
    ``ref.conv3x3_bias_relu_ref``, it accumulates and adds the bias in
    f32 and casts the output back to ``x.dtype`` (a no-op in f32).
    ``xla`` (the CPU default) is the engine's original
    ``_conv3x3_same_im2col`` einsum + separate bias/ReLU, bit-identical
    to what ``cnn_apply_fast`` always did.

    Keep the name: ``benchmarks/chip/metrics/conv_roofline.py`` finds the
    conv's device ops by this function's stack frame, so work moved out
    of it (or into it) leaves (or joins) the conv's measured time.
    """
    mode = resolve_kernel_mode(mode)
    act = jax.nn.relu if relu else (lambda v: v)
    if mode == "xla":
        from repro.models.cnn import _conv3x3_same_im2col
        return act(_conv3x3_same_im2col(x, w) + b)
    f32 = jnp.float32
    y = jax.lax.conv_general_dilated(
        x.reshape((-1,) + x.shape[-3:]), w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=f32)
    y = act(y + b.astype(f32)).astype(x.dtype)
    return y.reshape(x.shape[:-1] + y.shape[-1:])


def eval_head(feats: jnp.ndarray, wmat: jnp.ndarray, bias: jnp.ndarray,
              labels: jnp.ndarray, *, mode: str = "auto") -> jnp.ndarray:
    """Correct-prediction count of the classifier head (scalar int32).

    The fused path folds logits → argmax → compare → count into the
    matmul tiles; ``xla`` is the plain three-op chain.
    """
    mode = resolve_kernel_mode(mode)
    if mode == "xla":
        logits = feats @ wmat + bias
        pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.sum((pred == labels.astype(jnp.int32)).astype(jnp.int32))
    from . import ops
    return ops.eval_head(feats, wmat, bias, labels,
                         interpret=_interpret(mode))


# ------------------------------------------------- cold boot + baselines
# All three entries below are instances of the generalized coefficient
# aggregate (``kernels.coef_agg``): the tiny [n] coefficient recipe is
# computed here in XLA — matching each reference path's normalization
# bit-for-bit — and the heavy [n, L] weighted reduction runs fused.

def edge_aggregate_cold_batched(stacked_w: PyTree, valid: jnp.ndarray, *,
                                mode: str = "auto") -> PyTree:
    """Cold-boot edge mean for all N edges (eq. 2) —
    ``hieavg.edge_aggregate_cold_batched`` semantics, kernel-routed.

    stacked_w leaves ``[N, J, ...]``; ``valid`` [N, J].  Padded slots
    carry zero coefficient; an all-invalid edge aggregates to exact
    zeros (the 1e-12 denominator floor), never a division by zero.
    """
    mode = resolve_kernel_mode(mode)
    if mode == "xla":
        return hieavg.edge_aggregate_cold_batched(stacked_w, valid)
    from . import ops
    v = valid.astype(jnp.float32)
    pw = v / jnp.maximum(jnp.sum(v, axis=-1, keepdims=True), 1e-12)
    fn = functools.partial(ops.fused_coef_aggregate,
                           interpret=_interpret(mode))
    return jax.vmap(fn)(stacked_w, pw)


def global_aggregate_cold(stacked_w: PyTree, j_per_edge: jnp.ndarray, *,
                          mode: str = "auto") -> PyTree:
    """Cold-boot global J_i-weighted mean (eq. 3) —
    ``hieavg.global_aggregate_cold`` semantics, kernel-routed."""
    mode = resolve_kernel_mode(mode)
    if mode == "xla":
        return hieavg.global_aggregate_cold(stacked_w, j_per_edge)
    from . import ops
    pw = j_per_edge.astype(jnp.float32) \
        / jnp.maximum(jnp.sum(j_per_edge), 1e-12)
    return ops.fused_coef_aggregate(stacked_w, pw,
                                    interpret=_interpret(mode))


def fedavg(stacked_w: PyTree, part_weights: jnp.ndarray, *,
           mode: str = "auto") -> PyTree:
    """Weighted FedAvg — ``baselines.fedavg`` semantics, kernel-routed."""
    mode = resolve_kernel_mode(mode)
    if mode == "xla":
        return baselines.fedavg(stacked_w, part_weights)
    from . import ops
    coef = part_weights / jnp.maximum(jnp.sum(part_weights), 1e-12)
    return ops.fused_coef_aggregate(stacked_w, coef,
                                    interpret=_interpret(mode))


def delayed_grad(stacked_w: PyTree, mask: jnp.ndarray, pending: PyTree,
                 age: jnp.ndarray, beta, delta,
                 part_weights: jnp.ndarray, *, mode: str = "auto"
                 ) -> tuple[PyTree, PyTree, jnp.ndarray]:
    """Delayed-gradient aggregation — ``baselines.delayed_grad``
    semantics, kernel-routed.

    The aggregate is the pair form of the coefficient kernel: a present
    slot contributes ``coef·w``, a missing one its staleness-discounted
    pending update ``coef·p`` — the fill + weighted mean in one pass.
    The tiny pending/age store updates stay XLA (pure data movement).
    """
    mode = resolve_kernel_mode(mode)
    if mode == "xla":
        return baselines.delayed_grad(stacked_w, mask, pending, age,
                                      beta, delta, part_weights)
    from . import ops
    m = mask.astype(jnp.float32)
    k_prime = age + 1.0
    stale_c = (beta ** k_prime) * (k_prime <= delta).astype(jnp.float32)
    coef = part_weights * (m + (1.0 - m) * stale_c)
    coef = coef / jnp.maximum(jnp.sum(coef), 1e-12)
    agg = ops.fused_coef_aggregate_pair(stacked_w, pending, coef * m,
                                        coef * (1.0 - m),
                                        interpret=_interpret(mode))
    new_age = (age + 1.0) * (1.0 - m)
    return agg, stacked_w, new_age

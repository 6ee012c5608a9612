"""Kernel plane — fused Pallas kernels + the backend-dispatch layer.

One module per kernel, each with a pure-jnp oracle in ``ref.py`` that
defines its semantics contract (tests sweep shapes/dtypes against it):

  * ``hieavg_agg``      — fused HieAvg mix + history update (eq. 4/5),
                          one HBM pass instead of XLA's ~7,
  * ``sgd_update``      — the train-step masked SGD update,
  * ``eval_head``       — classifier-head eval: logits → argmax →
                          correct-count in one pass over the test set,
  * ``coef_agg``        — generalized coefficient-weighted aggregate
                          shared by the cold-boot means, FedAvg and the
                          delayed-gradient mix,
  * ``flash_attention`` — blocked online-softmax attention (the LLM
                          serving path).

``ops.py`` holds the jit'd pytree-level wrappers (batched/vmapped entry
points matching the engine's dense ``[N, J, ...]`` + validity-mask
conventions); ``dispatch.py`` is the backend policy — the
``kernel_mode = "auto" | "pallas" | "interpret" | "xla"`` knob that routes
the engine's hot path to the compiled kernel on TPU, the pure-XLA
reference on CPU, or the Pallas interpreter for validation.  The fused
modes cover every heavy phase of the engine round
(``dispatch.ROUND_PHASES``); the CNN conv block among them is XLA's own
convolution, fused with its bias and ReLU, not a Pallas kernel.  See
docs/ARCHITECTURE.md §Kernel plane for the layer contract.
"""
from .dispatch import (KERNEL_MODES, ROUND_PHASES, default_interpret,
                       fused_phase_coverage, resolve_kernel_mode)
from .ops import (eval_head, flash_attention, fused_coef_aggregate,
                  fused_coef_aggregate_pair, fused_edge_aggregate,
                  fused_edge_aggregate_batched, fused_mix_and_update,
                  fused_sgd_update)

__all__ = [
    "KERNEL_MODES", "ROUND_PHASES", "default_interpret",
    "fused_phase_coverage", "resolve_kernel_mode",
    "eval_head", "flash_attention",
    "fused_coef_aggregate", "fused_coef_aggregate_pair",
    "fused_edge_aggregate", "fused_edge_aggregate_batched",
    "fused_mix_and_update", "fused_sgd_update",
]

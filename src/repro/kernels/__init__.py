"""Kernel plane — the Pallas kernels and the backend-dispatch layer.

One module per kernel, each with a pure-jnp oracle in ``ref.py`` that
defines its semantics contract (tests sweep shapes/dtypes against it),
and each with its pytree-level entries beside the kernel:

  * ``hieavg_agg``      — fused HieAvg mix + history update (eq. 4/5),
                          one HBM pass instead of XLA's ~7
                          (``fused_mix_and_update``,
                          ``fused_edge_aggregate_batched``),
  * ``coef_agg``        — generalized coefficient-weighted aggregate
                          shared by the cold-boot means, FedAvg and the
                          delayed-gradient mix (``fused_coef_aggregate``,
                          ``fused_coef_aggregate_pair``),
  * ``flash_attention`` — blocked online-softmax attention (the LLM
                          serving path) and its GQA front end.

``dispatch.py`` is the backend policy — the ``kernel_mode = "auto" |
"pallas" | "interpret" | "xla"`` knob that routes the engine's
aggregation phases to the compiled kernel on TPU, the pure-XLA reference
on CPU, or the Pallas interpreter for validation, and the CNN conv block
to XLA's own convolution (fused modes) or the im2col einsum (``xla``).
The train step's SGD update and the eval head are plain XLA on every
path.  See docs/ARCHITECTURE.md §Kernel plane for the layer contract.
"""
# Imported with the package: ``dispatch`` imports them lazily (they
# import from it), so the engine's first trace would otherwise pay the
# import of Pallas inside its trace time.
from . import coef_agg, hieavg_agg  # noqa: E402,F401

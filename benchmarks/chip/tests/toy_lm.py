"""A token language model for the tests, with frozen weights: a frozen
embedding and a frozen hidden layer, a trainable rank-``RANK`` adapter on
that layer, and a trainable head, over a vocabulary of ``VOCAB`` tokens
(above 256, so bfloat16 cannot hold every id).  Its loss is next-token
cross-entropy over [B, ``SEQ``] int32 tokens; float32 at JAX's default
precision.  It keeps the model module contract of ``models/cnn.py`` with
the optional ``frozen_params``; the frozen weights come from the
configuration's ``frozen_seed``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

VOCAB, WIDTH, RANK, SEQ = 1000, 32, 4, 8


def param_shapes(setting: dict) -> dict:
    return {"lora_a": (WIDTH, RANK), "lora_b": (RANK, WIDTH),
            "head": (WIDTH, VOCAB), "head_b": (VOCAB,)}


def _normal(shapes: dict, key) -> dict:
    return {name: jnp.zeros(shape, jnp.float32) if len(shape) == 1
            else jax.random.normal(jax.random.fold_in(key, i), shape,
                                   jnp.float32) / np.sqrt(shape[0])
            for i, (name, shape) in enumerate(sorted(shapes.items()))}


def frozen_params(config: dict) -> dict:
    shapes = {"embed": (VOCAB, WIDTH), "hidden": (WIDTH, WIDTH)}
    make = jax.jit(lambda key: _normal(shapes, key))
    return make(jax.random.key(int(config["frozen_seed"])))


def init_params(config: dict, seed: int) -> dict:
    make = jax.jit(lambda key: _normal(param_shapes(config["setting"]), key))
    return make(jax.random.key(int(seed) % 2 ** 32))


def logits(p, x, frozen):
    e = frozen["embed"][x]                                # [B, L, WIDTH]
    h = jnp.tanh(e @ frozen["hidden"] + (e @ p["lora_a"]) @ p["lora_b"])
    return h @ p["head"] + p["head_b"]


def loss(p, x, y, frozen):
    logp = jax.nn.log_softmax(logits(p, x, frozen), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))


@jax.jit
def test_count(p, test_x, test_y, frozen):
    return jnp.sum(jnp.argmax(logits(p, test_x, frozen), axis=-1) == test_y)


def n_eval(planes: dict) -> int:
    """One target per token."""
    return int(np.size(planes["test_y"]))


def train_flops_per_sample(setting: dict) -> int:
    """A sequence of ``SEQ`` tokens: forward through all layers; weight
    gradients of the adapter and the head; input gradients through the
    head and the adapter's second factor (nothing below the frozen layer
    is trained, so no gradient flows into the embedding)."""
    fwd = WIDTH * WIDTH + 2 * WIDTH * RANK + WIDTH * VOCAB
    dw = 2 * WIDTH * RANK + WIDTH * VOCAB
    dx = WIDTH * VOCAB + RANK * WIDTH
    return 2 * SEQ * (fwd + dw + dx)

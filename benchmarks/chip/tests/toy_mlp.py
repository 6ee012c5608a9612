"""A client model other than the CNN, for the tests: one hidden layer of
``HIDDEN`` ReLU units over the flattened image, float32, at JAX's default
precision.  It keeps the model module contract of ``models/cnn.py`` and
has no kernel counts of its own."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIDDEN = 32


def param_shapes(setting: dict) -> dict:
    d = setting["image_hw"] ** 2
    return {"w1": (d, HIDDEN), "b1": (HIDDEN,),
            "w2": (HIDDEN, setting["n_classes"]),
            "b2": (setting["n_classes"],)}


def init_params(config: dict, seed: int) -> dict:
    shapes = param_shapes(config["setting"])

    @jax.jit
    def make(key):
        return {name: jnp.zeros(shape, jnp.float32) if len(shape) == 1
                else jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32) / np.sqrt(shape[0])
                for i, (name, shape) in enumerate(sorted(shapes.items()))}

    return make(jax.random.key(int(seed) % 2 ** 32))


def logits(p, x):
    h = jax.nn.relu(x.reshape(x.shape[0], -1) @ p["w1"] + p["b1"])
    return h @ p["w2"] + p["b2"]


def loss(p, x, y):
    logp = jax.nn.log_softmax(logits(p, x), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


@jax.jit
def test_count(p, test_x, test_y):
    return jnp.sum(jnp.argmax(logits(p, test_x), axis=-1) == test_y)


def n_eval(planes: dict) -> int:
    return len(planes["test_y"])


def train_flops_per_sample(setting: dict) -> int:
    """Forward and weight gradients of both layers, and the input
    gradient of the second (the first layer's input is data)."""
    d, c = setting["image_hw"] ** 2, setting["n_classes"]
    fwd = d * HIDDEN + HIDDEN * c
    return 2 * (fwd + fwd + HIDDEN * c)

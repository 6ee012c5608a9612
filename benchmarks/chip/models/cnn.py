"""The paper's CNN (arXiv:2308.01296 Sec. 6.1.5), written plainly.

conv 3x3 (1 -> c1) + ReLU, conv 3x3 (c1 -> c2) + ReLU, 2x2 max-pool,
dense (hw/2 * hw/2 * c2 -> classes), SAME padding, float32.  Every conv,
like the dense layer, is one matmul, at JAX's default precision, which
the configuration states.  It imports nothing of the program.

A configuration names its client model by ``"model": "<name>"``; the
harness loads ``models/<name>.py`` by path, and nothing else in it reads
a size of the model.  Every model module keeps this contract:

    param_shapes(setting)   leaf name -> shape of the global model
    init_params(config, seed)
                            the initial global model from ``seed``, made
                            on the device in one jitted call
    loss(p, x, y)           mean training loss of a batch
    test_count(p, x, y)     eval targets the model gets right (jitted)
    n_eval(planes)          eval targets in the deployment's test split
    train_flops_per_sample(setting)
                            FLOPs of one training sample's forward,
                            weight gradients and input gradients

Counts of a kernel's own work, such as ``conv_work`` here, are optional;
a metric that needs one reads nothing where the model has none.  A
multiply-add is two FLOPs.

Optional parts, which this CNN does not have:

    frozen_params(config)   weights that every device slot shares and no
                            step changes (a pretrained backbone), a flat
                            dict made on the device in one jitted call
                            from a seed fixed in the configuration, not
                            the run's.  A model with them takes them as a
                            fourth argument, ``loss(p, x, y, frozen)`` and
                            ``test_count(p, x, y, frozen)``; its
                            ``param_shapes`` are then the federated
                            parameters alone (what is trained, exchanged
                            and aggregated, and all that
                            ``aggregate_roofline`` and the update gaps of
                            ``correct`` read), and its
                            ``train_flops_per_sample`` counts the forward
                            pass, the input gradients through the frozen
                            layers, and the weight gradients of the
                            federated parameters only.
    reference_block(setting)
                            device slots that the plain reference trains
                            at once (default: all of them), for a model
                            whose activations for every slot would not fit
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = 4


# ----------------------------------------------------------- the model
def param_shapes(setting: dict) -> dict:
    s = setting
    hw, c1, c2, ncls = s["image_hw"], s["cnn_c1"], s["cnn_c2"], s["n_classes"]
    return {"conv1": (3, 3, 1, c1), "b1": (c1,),
            "conv2": (3, 3, c1, c2), "b2": (c2,),
            "dense": ((hw // 2) * (hw // 2) * c2, ncls), "b3": (ncls,)}


def init_params(config: dict, seed: int) -> dict:
    """Initial global model from ``seed``, made on the device in one
    jitted call: weights normal with variance 1/fan_in, biases zero."""
    shapes = param_shapes(config["setting"])
    key32 = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            if len(shape) == 1:
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                fan_in = int(np.prod(shape[:-1]))
                out[name] = jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32) \
                    / np.sqrt(fan_in)
        return out

    return make(jax.random.key(key32))


def conv3x3_same(x, w):
    """x [B, H, W, Cin], w [3, 3, Cin, Cout]: SAME 3x3 conv as one matmul
    of the nine shifted taps (im2col, (i, j, c) order) with the weights."""
    _, h, wd, cin = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    cols = jnp.concatenate([xp[:, i:i + h, j:j + wd, :]
                            for i in range(3) for j in range(3)], axis=-1)
    return cols @ w.reshape(9 * cin, w.shape[-1])


def logits(p, x):
    x = jax.nn.relu(conv3x3_same(x, p["conv1"]) + p["b1"])
    x = jax.nn.relu(conv3x3_same(x, p["conv2"]) + p["b2"])
    b, h, w, c = x.shape
    x = x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
    return x.reshape(b, -1) @ p["dense"] + p["b3"]


def loss(p, x, y):
    logp = jax.nn.log_softmax(logits(p, x), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


@jax.jit
def test_count(p, test_x, test_y):
    return jnp.sum(jnp.argmax(logits(p, test_x), axis=-1) == test_y)


def n_eval(planes: dict) -> int:
    """One label per test image."""
    return len(planes["test_y"])


# ------------------------------------------------------------ its work
def conv_layers(s: dict) -> list[tuple[int, int, int, bool]]:
    """``(pixels, c_in, c_out, needs_dx)`` of each conv layer.  The first
    layer's input is data, so its input gradient is never computed."""
    px = s["image_hw"] ** 2
    return [(px, 1, s["cnn_c1"], False), (px, s["cnn_c1"], s["cnn_c2"], True)]


def train_flops_per_sample(s: dict) -> int:
    """Forward, weight gradients and input gradients of one training
    sample (88.36 MFLOP at the paper's widths)."""
    dense = (s["image_hw"] // 2) ** 2 * s["cnn_c2"] * s["n_classes"]
    fwd = sum(px * 9 * ci * co for px, ci, co, _ in conv_layers(s)) + dense
    dx = sum(px * 9 * ci * co for px, ci, co, dxn in conv_layers(s) if dxn) \
        + dense
    return 2 * (fwd + fwd + dx)


def conv_work(s: dict, train_samples: int, eval_samples: int
              ) -> tuple[float, float]:
    """Least ``(FLOPs, bytes)`` of the conv blocks (matmul, bias, ReLU)
    for ``train_samples`` forward and backward and ``eval_samples``
    forward.  Bytes: forward reads the input and writes the output once;
    backward reads the output gradient, the output (for the ReLU mask) and
    the input (for the weight gradient), and writes the input gradient
    where it is needed; each training step of ``batch_size`` samples
    reads the weights twice and writes their gradient once.  Activations
    are float32, as the configuration states."""
    flops = bytes_ = 0.0
    for px, ci, co, dxn in conv_layers(s):
        mac = px * 9 * ci * co
        w = F32 * (9 * ci * co + co)
        fwd_b = F32 * px * (ci + co)
        bwd_b = F32 * px * (2 * co + ci + (ci if dxn else 0))
        flops += train_samples * 2 * mac * (3 if dxn else 2) \
            + eval_samples * 2 * mac
        bytes_ += train_samples * (fwd_b + bwd_b + 3 * w / s["batch_size"]) \
            + eval_samples * fwd_b
    return flops, bytes_

"""Plain reference of a BHFL deployment's first global rounds.

Written from the paper (arXiv:2308.01296): every device trains the Sec.
6.1.5 CNN (3x3 conv, ReLU, 3x3 conv, ReLU, 2x2 max-pool, dense) for one
local epoch of SGD per edge round; each edge averages its devices (eq. 2
during the T_c cold-boot rounds, eq. 4 with HieAvg's straggler estimate
after them); the leader averages the edges weighted by J_i (eqs. 3 and
5); the global model is scored on the test split; the simulated clock
waits for the slowest submitting edge plus the consensus stall (C2), and
the consensus energy accumulates.

It imports nothing of the program.  It reads the deployment's input
planes (data, batch indices, submission masks, per-device time draws and
per-round consensus draws, as the seed made them) and the benchmark's own
initial weights, and computes everything else itself, in the dtype it is
given: float32 is the configuration's precision, bfloat16 the control.
Every conv, like the dense layer, is one matmul, at JAX's default
precision, which the configuration states.

``fault`` plants one of the faults the check must catch, for reading
their limits on the chip: ``"half_batch"`` (each step's loss is the mean
over the first half of its batch), ``"no_exchange"`` (each edge takes its
first device's model instead of aggregating), ``"altered_update"``
(device slot (0, 0)'s local update counts double).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = (None, "half_batch", "no_exchange", "altered_update")


# --------------------------------------------------------------- the model
def param_shapes(config: dict) -> dict:
    s = config["setting"]
    hw, c1, c2, ncls = s["image_hw"], s["cnn_c1"], s["cnn_c2"], s["n_classes"]
    return {"conv1": (3, 3, 1, c1), "b1": (c1,),
            "conv2": (3, 3, c1, c2), "b2": (c2,),
            "dense": ((hw // 2) * (hw // 2) * c2, ncls), "b3": (ncls,)}


def init_params(config: dict, seed: int) -> dict:
    """Initial global model from ``seed``, made on the device in one
    jitted call: weights normal with variance 1/fan_in, biases zero."""
    shapes = param_shapes(config)
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


# ------------------------------------------------------------ one device
def local_epoch(p, xs, ys, lr, half):
    """SGD over ``xs`` [steps, B, ...]; returns (params, mean step loss)."""
    def step(p, xy):
        x, y = xy
        if half:
            x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
        v, g = jax.value_and_grad(loss)(p, x, y)
        return jax.tree.map(lambda w, gw: w - lr * gw, p, g), v
    p, vs = jax.lax.scan(step, p, (xs, ys))
    return p, jnp.mean(vs)


# ------------------------------------------------------------ HieAvg layer
def hieavg(ws, mask, hist, pw, gamma0, lam, warm):
    """One aggregation over participants (leading axis) with the history
    update.  Cold: the pw-weighted mean.  Warm: a straggler's submission
    is replaced by its estimate prev + E[delta], weighted by
    gamma0 * lam**k' (k' its consecutive misses, this one included)."""
    prev, dmean, n_obs, miss = hist
    m = mask.astype(pw.dtype)
    gamma = gamma0 * lam ** (miss + 1)
    coef = pw * (m + (1 - m) * gamma) if warm else pw

    def each(w, pv, dm):
        mb = m.reshape(m.shape + (1,) * (w.ndim - 1))
        cb = coef.reshape(mb.shape)
        est = pv + dm
        agg = jnp.sum(cb * (mb * w + (1 - mb) * est), axis=0) if warm \
            else jnp.sum(cb * w, axis=0)
        nb = n_obs.reshape(mb.shape)
        new_prev = mb * w + (1 - mb) * est
        new_dmean = mb * (dm * nb + (w - pv)) / (nb + 1) + (1 - mb) * dm
        return agg, new_prev, new_dmean

    out = {k: each(ws[k], prev[k], dmean[k]) for k in ws}
    return ({k: v[0] for k, v in out.items()},
            ({k: v[1] for k, v in out.items()},
             {k: v[2] for k, v in out.items()},
             n_obs + m, (miss + 1) * (1 - m)))


def new_history(ws):
    n = next(iter(ws.values())).shape[0]
    dt = next(iter(ws.values())).dtype
    return (ws, {k: jnp.zeros_like(v) for k, v in ws.items()},
            jnp.zeros((n,), dt), jnp.zeros((n,), dt))


# ------------------------------------------------------------- the rounds
@partial(jax.jit, static_argnames=("warm", "first", "fault"))
def edge_round(dev_w, ehist, train_x, train_y, bidx, has, valid, dmask, lr,
               gamma0, lam, *, warm, first, fault):
    """K-th edge round of all edges: local epochs, then each edge's
    aggregation; returns (device models synced to their edge model,
    edge history, device losses [N, J])."""
    N, J = valid.shape
    x = train_x[bidx] * has[:, :, None, None, None, None, None]
    y = jnp.where(has[:, :, None, None] > 0, train_y[bidx], 0)
    one = partial(local_epoch, half=fault == "half_batch")
    train = jax.vmap(jax.vmap(one, in_axes=(0, 0, 0, None)),
                     in_axes=(0, 0, 0, None))
    ws, dev_loss = train(dev_w, x, y, lr)
    if fault == "altered_update":
        ws = jax.tree.map(lambda a, b: a.at[0, 0].add(a[0, 0] - b[0, 0]),
                          ws, dev_w)
    if first:
        ehist = jax.vmap(new_history)(ws)
    v = valid.astype(lr.dtype)
    floor = 1.0 if warm else 1e-12
    pw = v / jnp.maximum(jnp.sum(v, axis=1, keepdims=True), floor)
    agg, ehist = jax.vmap(partial(hieavg, warm=warm),
                          in_axes=(0, 0, 0, 0, None, None))(
        ws, dmask, ehist, pw, gamma0, lam)
    if fault == "no_exchange":
        agg = {k: w[:, 0] for k, w in ws.items()}
    synced = {k: jnp.broadcast_to(a[:, None], (N, J) + a.shape[1:])
              for k, a in agg.items()}
    return synced, ehist, dev_loss


@partial(jax.jit, static_argnames=("warm", "first"))
def global_round(edge_w, ghist, emask, j_arr, gamma0, lam, *, warm, first):
    if first:
        ghist = new_history(edge_w)
    floor = 1.0 if warm else 1e-12
    pw = j_arr / jnp.maximum(jnp.sum(j_arr), floor)
    return hieavg(edge_w, emask, ghist, pw, gamma0, lam, warm)


@jax.jit
def test_count(p, test_x, test_y):
    return jnp.sum(jnp.argmax(logits(p, test_x), axis=-1) == test_y)


def round_time(dev_time_t, valid, emask, j_arr, cons_t, edge_hop):
    """Simulated seconds of one global round: per edge the slowest valid
    device of each edge round, summed over the K rounds; the leader waits
    for the slowest submitting edge (every edge when none submitted), the
    edge-leader hop, and whatever of consensus does not hide in that wait."""
    el = jnp.max(jnp.where(valid[None], dev_time_t, 0), axis=2)   # [K, N]
    window = jnp.sum(el, axis=0)
    real = j_arr > 0
    sub = emask & real
    w = jnp.where(jnp.any(sub), jnp.max(jnp.where(sub, window, 0)),
                  jnp.max(jnp.where(real, window, 0)))
    return w + edge_hop + jnp.maximum(0, cons_t - w)


def run(config: dict, planes: dict, init_w: dict, rounds: int,
        dtype=jnp.float32, fault=None) -> dict:
    """The first ``rounds`` global rounds.  Returns per-round ``loss``,
    ``correct`` (test images right), ``clock`` and ``energy``, and the
    global model after each round (``models``)."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    s = config["setting"]
    K, t_cold = s["k_edge_rounds"], s["t_cold_boot"]
    f = lambda a: jnp.asarray(a, dtype)                      # noqa: E731
    train_x, test_x = f(planes["train_x"]), f(planes["test_x"])
    train_y, test_y = jnp.asarray(planes["train_y"]), jnp.asarray(
        planes["test_y"])
    has, valid = f(planes["has_data"]), jnp.asarray(planes["valid"])
    j_arr = f(planes["j_arr"])
    gamma0, lam = f(s["gamma0"]), f(s["lam"])
    N, J = valid.shape
    g = {k: f(v) for k, v in init_w.items()}
    dev_w = {k: jnp.broadcast_to(v, (N, J) + v.shape)
             for k, v in g.items()}
    ehist = ghist = None
    clock = energy = f(0.0)
    out = {"loss": [], "correct": [], "clock": [], "energy": [], "models": []}
    for t in range(rounds):
        warm = t + 1 > t_cold
        for k in range(K):
            r = t * K + k
            lr = f(1.0 / (1.0 / s["lr0"] + s["lr_decay"] * r))
            dev_w, ehist, dev_loss = edge_round(
                dev_w, ehist, train_x, train_y, planes["batch_idx"][t, k],
                has, valid, planes["dev_masks"][t, k], lr, gamma0, lam,
                warm=warm, first=r == 0, fault=fault)
        edge_w = {k: v[:, 0] for k, v in dev_w.items()}
        g, ghist = global_round(edge_w, ghist, planes["edge_masks"][t], j_arr,
                                gamma0, lam, warm=warm, first=t == 0)
        dev_w = {k: jnp.broadcast_to(v, (N, J) + v.shape)
                 for k, v in g.items()}
        vf = valid.astype(dtype)
        out["loss"].append(jnp.sum(dev_loss * vf)
                           / jnp.maximum(jnp.sum(vf), 1))
        out["correct"].append(test_count(g, test_x, test_y))
        clock = clock + round_time(f(planes["dev_time"][t]), valid,
                                   jnp.asarray(planes["edge_masks"][t]), j_arr,
                                   f(planes["cons_time"][t]),
                                   f(planes["edge_hop"]))
        energy = energy + f(planes["cons_energy"][t])
        out["clock"].append(clock)
        out["energy"].append(energy)
        out["models"].append(g)
    res = {k: np.asarray(jnp.stack(out[k]).astype(jnp.float32))
           for k in ("loss", "correct", "clock", "energy")}
    res["models"] = [{k: np.asarray(v.astype(jnp.float32))
                      for k, v in m.items()} for m in out["models"]]
    return res

"""Plain reference of a BHFL deployment's first global rounds.

Written from the paper (arXiv:2308.01296): every device trains the
client model for one local epoch of SGD per edge round; each edge
averages its devices (eq. 2 during the T_c cold-boot rounds, eq. 4 with
HieAvg's straggler estimate after them); the leader averages the edges
weighted by J_i (eqs. 3 and 5); the global model is scored on the test
split; the simulated clock waits for the slowest submitting edge plus the
consensus stall (C2), and the consensus energy accumulates.

The client model is a module of ``models/`` that the configuration names
(its contract is in ``models/cnn.py``); this module reads only its
``loss``, ``test_count`` and, where it has them, ``reference_block`` and
frozen weights, and no size of it.  Frozen weights are the same for
every device slot: they enter each jitted function as an argument, are
never broadcast to slots or aggregated, and no gradient is taken of
them.  ``reference_block`` device slots train at once (all of them where
the module does not say), one block after another.

It imports nothing of the program.  It reads the deployment's input
planes (data, batch indices, submission masks, per-device time draws and
per-round consensus draws, as the seed made them) and the benchmark's own
initial weights, and computes everything else itself, in the dtype it is
given: float32 is the configuration's precision, bfloat16 the control.
Integer planes, such as token ids, keep their own dtype.

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


def _with_frozen(frozen):
    """The trailing arguments of a model's ``loss`` and ``test_count``."""
    return () if frozen is None else (frozen,)


# ------------------------------------------------------------ one device
def local_epoch(model, p, xs, ys, lr, half, frozen=None):
    """SGD on ``model.loss`` over ``xs`` [steps, B, ...]; returns
    (params, mean step loss).  The gradient is taken of ``p`` alone."""
    def step(p, xy):
        x, y = xy
        if half:
            x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
        v, g = jax.value_and_grad(model.loss)(p, x, y, *_with_frozen(frozen))
        return jax.tree.map(lambda w, gw: w - lr * gw, p, g), v
    p, vs = jax.lax.scan(step, p, (xs, ys))
    return p, jnp.mean(vs)


def train_slots(one, dev_w, x, y, lr, block):
    """``one`` local epoch on every device slot of ``dev_w`` [N, J, ...],
    ``block`` slots at a time.  A block of all N x J slots is one vmapped
    computation; smaller blocks run one after another over the flattened
    slots (the last one shorter where ``block`` does not divide them), so
    only one block's activations are live at once."""
    N, J = x.shape[:2]
    if block >= N * J:
        train = jax.vmap(jax.vmap(one, in_axes=(0, 0, 0, None)),
                         in_axes=(0, 0, 0, None))
        return train(dev_w, x, y, lr)
    slots = jax.tree.map(lambda a: a.reshape((N * J,) + a.shape[2:]),
                         (dev_w, x, y))
    train = jax.vmap(one, in_axes=(0, 0, 0, None))
    split = N * J // block * block
    blocks = jax.tree.map(
        lambda v: v[:split].reshape((-1, block) + v.shape[1:]), slots)
    full = jax.lax.map(lambda a: train(*a, lr), blocks)
    parts = [jax.tree.map(lambda v: v.reshape((split,) + v.shape[2:]), full)]
    if split < N * J:
        parts.append(train(*jax.tree.map(lambda v: v[split:], slots), lr))
    out = jax.tree.map(lambda *vs: jnp.concatenate(vs), *parts)
    return jax.tree.map(lambda v: v.reshape((N, J) + v.shape[1:]), out)


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
@partial(jax.jit, static_argnames=("model", "warm", "first", "fault",
                                   "block"))
def edge_round(dev_w, ehist, train_x, train_y, bidx, has, valid, dmask, lr,
               gamma0, lam, frozen, *, model, warm, first, fault, block):
    """K-th edge round of all edges: local epochs, ``block`` device slots
    at a time, then each edge's aggregation; returns (device models synced
    to their edge model, edge history, device losses [N, J])."""
    N, J = valid.shape
    x, y = train_x[bidx], train_y[bidx]          # [N, J, steps, B, ...]
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = x * has.reshape(has.shape + (1,) * (x.ndim - 2))
    else:
        x = jnp.where(has.reshape(has.shape + (1,) * (x.ndim - 2)) > 0, x, 0)
    y = jnp.where(has.reshape(has.shape + (1,) * (y.ndim - 2)) > 0, y, 0)
    one = partial(local_epoch, model, half=fault == "half_batch",
                  frozen=frozen)
    ws, dev_loss = train_slots(one, dev_w, x, y, lr, block)
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


def run(model, config: dict, planes: dict, init_w: dict, rounds: int,
        dtype=jnp.float32, fault=None, frozen: dict | None = None) -> dict:
    """The first ``rounds`` global rounds of ``model``, over its
    ``frozen`` weights where it has them.  Returns per-round ``loss``,
    ``correct`` (eval targets right), ``clock`` and ``energy``, and the
    global model after each round (``models``)."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    s = config["setting"]
    K, t_cold = s["k_edge_rounds"], s["t_cold_boot"]
    f = lambda a: jnp.asarray(a, dtype)                      # noqa: E731

    def fp(a):
        """``a`` in the run's dtype where it is floating."""
        return f(a) if jnp.issubdtype(a.dtype, jnp.floating) \
            else jnp.asarray(a)

    train_x, test_x = fp(planes["train_x"]), fp(planes["test_x"])
    frozen = None if frozen is None else {k: fp(v) for k, v in frozen.items()}
    train_y, test_y = jnp.asarray(planes["train_y"]), jnp.asarray(
        planes["test_y"])
    has, valid = f(planes["has_data"]), jnp.asarray(planes["valid"])
    j_arr = f(planes["j_arr"])
    gamma0, lam = f(s["gamma0"]), f(s["lam"])
    N, J = valid.shape
    block = getattr(model, "reference_block", lambda _: N * J)(s)
    if block < 1:
        raise ValueError(f"reference_block {block} trains no device slot")
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
                frozen, model=model, warm=warm, first=r == 0, fault=fault,
                block=block)
        edge_w = {k: v[:, 0] for k, v in dev_w.items()}
        g, ghist = global_round(edge_w, ghist, planes["edge_masks"][t], j_arr,
                                gamma0, lam, warm=warm, first=t == 0)
        dev_w = {k: jnp.broadcast_to(v, (N, J) + v.shape)
                 for k, v in g.items()}
        vf = valid.astype(dtype)
        out["loss"].append(jnp.sum(dev_loss * vf)
                           / jnp.maximum(jnp.sum(vf), 1))
        out["correct"].append(model.test_count(g, test_x, test_y,
                                               *_with_frozen(frozen)))
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

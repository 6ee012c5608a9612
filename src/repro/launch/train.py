"""End-to-end hierarchical BHFL SPMD training driver.

Runs the paper's full workflow at framework scale: K edge rounds per global
round, HieAvg at both layers, Raft consensus latency accounting, straggler
schedules, checkpointing.  On this CPU container use ``--smoke`` (reduced
arch, debug mesh); on a TPU pod the same driver runs the production mesh.

By default the T×K rounds run engine-style (``fused=True``): batches,
masks, and the lr schedule are precomputed host-side, the Raft chain is
replayed up front (its per-round election+commit latency feeds a
simulated clock, like ``repro.fl.engine``), and the whole run is ONE
``lax.scan``-compiled program instead of a Python dispatch per edge
round.  ``fused=False`` keeps the original per-round loop (periodic
mid-run checkpoints; otherwise identical math — the fused path consumes
the batch/chain RNG streams in the same order).

``kernel_mode`` is the kernel-plane knob (``repro.kernels.dispatch``):
``"auto"`` resolves to the Pallas flash-attention kernel on TPU and
the XLA einsum path on CPU.  This driver refuses ``"interpret"`` — the
Pallas interpreter is a test/validation tool, not a production path.

  PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \\
      --smoke --steps 20 --batch 4 --seq 64
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.core import (LatencyParams, RaftChain, RaftParams, straggler,
                        stream_rng, stream_seed)
from repro.data import lm_tokens
from repro.kernels.dispatch import KERNEL_MODES, resolve_kernel_mode
from repro.launch.inputs import _memory_shape
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.launch.steps import init_fl_histories, make_hfl_train_step
from repro.models import attention, init_from_specs, param_specs
from repro.optim import paper_lr


def run(arch: str, *, smoke: bool = True, steps: int = 20, k_edge: int = 2,
        n_clients: int = 2, batch: int = 4, seq: int = 64,
        straggler_frac: float = 0.2, gamma0: float = 0.9, lam: float = 0.9,
        normalize: bool = True, ckpt_dir: str | None = None,
        seed: int = 0, progress: bool = True, fused: bool = True,
        kernel_mode: str = "auto",
        lat_params: LatencyParams | None = None) -> dict:
    kernel_mode = resolve_kernel_mode(kernel_mode)
    if kernel_mode == "interpret":
        raise ValueError(
            "train.run(kernel_mode='interpret'): the Pallas interpreter is "
            "a test/validation path, not a training backend — use 'auto', "
            "'pallas' (TPU), or 'xla'")
    cfg = get_smoke(arch) if smoke else get_config(arch)
    mesh = make_debug_mesh() if smoke else make_production_mesh()
    e, c = 1 if smoke else 2, n_clients

    key = jax.random.key(seed)
    base = init_from_specs(param_specs(cfg), key)
    params = jax.tree.map(lambda x: jnp.broadcast_to(x, (e, c) + x.shape),
                          base)
    dev_hist, glob_hist = init_fl_histories(params)
    step = make_hfl_train_step(
        cfg, gamma0=gamma0, lam=lam, normalize=normalize,
        mesh=None if smoke else mesh)

    # straggler schedules + Raft chain (the BHFL control plane).  Each
    # consumer gets its own SeedSequence stream (core.rng) — the same
    # registry the CNN simulator uses, so no two schedules ever collide.
    dev_masks = straggler.from_fraction(steps * k_edge + 1, e * c,
                                        straggler_frac,
                                        seed=stream_seed(seed, "dev_masks"))
    edge_masks = straggler.from_fraction(steps + 1, e, straggler_frac,
                                         seed=stream_seed(seed, "edge_masks"))
    lp = lat_params or LatencyParams(T=steps, N=e, J=c)
    chain = RaftChain(max(e, 1), RaftParams(),
                      seed=stream_seed(seed, "chain"))

    data = lm_tokens(e * c * batch * 4, seq + 1, cfg.vocab,
                     seed=stream_seed(seed, "data"))
    ms = _memory_shape(cfg)
    rng = stream_rng(seed, "batches")

    prev_flash = attention.USE_FLASH_KERNEL
    attention.USE_FLASH_KERNEL = kernel_mode == "pallas"
    try:
        return _run_timed(cfg, mesh, step, params, dev_hist, glob_hist,
                          chain, dev_masks, edge_masks, data, ms, rng, lp,
                          steps=steps, k_edge=k_edge, e=e, c=c, batch=batch,
                          seq=seq, progress=progress, fused=fused,
                          ckpt_dir=ckpt_dir)
    finally:
        attention.USE_FLASH_KERNEL = prev_flash


def _run_timed(cfg, mesh, step, params, dev_hist, glob_hist, chain,
               dev_masks, edge_masks, data, ms, rng, lp, *, steps, k_edge,
               e, c, batch, seq, progress, fused, ckpt_dir) -> dict:
    t0 = time.time()
    if fused:
        out = _run_fused(cfg, mesh, step, params, dev_hist, glob_hist,
                         chain, dev_masks, edge_masks, data, ms, rng, lp,
                         steps=steps, k_edge=k_edge, e=e, c=c, batch=batch,
                         seq=seq, progress=progress)
        glob = out.pop("global_model")
        if ckpt_dir:
            save_checkpoint(ckpt_dir, steps, glob,
                            metadata={"round": steps,
                                      "block": len(chain.blocks) - 1})
        return {**out, "wall": time.time() - t0,
                "blocks": len(chain.blocks) - 1,
                "chain_valid": chain.validate()}

    step = jax.jit(step)
    losses = []
    with mesh:
        for t in range(steps):
            chain.elect_leader()
            for k in range(k_edge):
                idx = rng.integers(0, data.shape[0], e * c * batch)
                chunk = data[idx].reshape(e, c, batch, seq + 1)
                b = {"tokens": jnp.asarray(chunk[..., :-1]),
                     "labels": jnp.asarray(chunk[..., 1:])}
                if ms is not None:
                    b["memory"] = jnp.zeros((e, c, batch) + ms,
                                            cfg.jnp_param_dtype)
                dm = jnp.asarray(dev_masks[t * k_edge + k].reshape(e, c))
                em = jnp.asarray(edge_masks[t])
                lr = paper_lr(jnp.asarray(t * k_edge + k, jnp.float32),
                              1e-2, 0.3)
                params, dev_hist, glob_hist, loss = step(
                    params, dev_hist, glob_hist, b, dm, em, lr)
            chain.commit_block(f"edges@{t}", f"global@{t}")
            losses.append(float(loss))
            if progress and (t % 5 == 0 or t == steps - 1):
                print(f"  global round {t:3d}  loss {losses[-1]:.4f}")
            if ckpt_dir and (t + 1) % 10 == 0:
                glob = jax.tree.map(lambda x: np.asarray(x[0, 0]), params)
                save_checkpoint(ckpt_dir, t + 1, glob,
                                metadata={"round": t + 1,
                                          "block": len(chain.blocks) - 1})
    return {"losses": losses, "wall": time.time() - t0,
            "blocks": len(chain.blocks) - 1, "chain_valid": chain.validate()}


def _run_fused(cfg, mesh, step, params, dev_hist, glob_hist, chain,
               dev_masks, edge_masks, data, ms, rng, lp: LatencyParams, *,
               steps: int, k_edge: int, e: int, c: int, batch: int,
               seq: int, progress: bool) -> dict:
    """The engine path: all T×K rounds as ONE ``lax.scan``-compiled program.

    Batches are drawn host-side in the same (t, k) order as the legacy
    loop (same ``rng`` stream → identical indices), the Raft chain is
    replayed up front (same election winners, same block chain), and the
    scan consumes stacked per-round arrays — one compile and one dispatch
    for the whole run, the same orchestration the CNN engine
    (``repro.fl.engine``) uses.

    Latency accounting is expectation-level (this driver has no per-device
    time draws): each global round costs the K-round edge window
    ``k_edge * (2 lm_device + lp_device)``, the edge<->leader hop, and any
    consensus stall ``max(0, L_bc - window)`` with L_bc the replayed
    election+commit elapsed — the same C2 semantics as the CNN engine.
    """
    R = steps * k_edge
    idx = np.stack([rng.integers(0, data.shape[0], e * c * batch)
                    for _ in range(R)])                   # legacy draw order
    chunks = data[idx].reshape(R, e, c, batch, seq + 1)
    tokens = jnp.asarray(chunks[..., :-1])
    labels = jnp.asarray(chunks[..., 1:])
    dms = jnp.asarray(dev_masks[:R].reshape(R, e, c))
    ems = jnp.asarray(edge_masks[np.arange(R) // k_edge])
    lrs = paper_lr(jnp.arange(R, dtype=jnp.float32), 1e-2, 0.3)

    cons = np.zeros(steps)
    for t in range(steps):
        _, t_elect = chain.elect_leader()
        _, t_commit = chain.commit_block(f"edges@{t}", f"global@{t}")
        cons[t] = t_elect + t_commit
    window = k_edge * (2.0 * lp.lm_device + lp.lp_device)
    sim_clock = np.cumsum(window + 2.0 * lp.lm_edge
                          + np.maximum(0.0, cons - window))

    def body(carry, xs):
        params, dev_hist, glob_hist = carry
        tk, lb, dm, em, lr = xs
        b = {"tokens": tk, "labels": lb}
        if ms is not None:
            b["memory"] = jnp.zeros((e, c, batch) + ms, cfg.jnp_param_dtype)
        params, dev_hist, glob_hist, loss = step(
            params, dev_hist, glob_hist, b, dm, em, lr)
        return (params, dev_hist, glob_hist), loss

    @jax.jit
    def fused(carry, xs):
        return jax.lax.scan(body, carry, xs)

    with mesh:
        (params, dev_hist, glob_hist), losses_r = fused(
            (params, dev_hist, glob_hist), (tokens, labels, dms, ems, lrs))
    # the legacy loop reports each global round's LAST edge-round loss
    losses = [float(x) for x in
              np.asarray(losses_r).reshape(steps, k_edge)[:, -1]]
    if progress:
        for t in range(steps):
            if t % 5 == 0 or t == steps - 1:
                print(f"  global round {t:3d}  loss {losses[t]:.4f}  "
                      f"clock {sim_clock[t]:.1f}s")
    return {"losses": losses, "sim_clock": sim_clock,
            "global_model": jax.tree.map(lambda x: np.asarray(x[0, 0]),
                                         params)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="h2o-danube-1.8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--k-edge", type=int, default=2)
    ap.add_argument("--clients", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--kernel-mode", default="auto",
                    choices=[m for m in KERNEL_MODES if m != "interpret"],
                    help="kernel-plane backend (auto resolves per device; "
                         "'interpret' is test-only and refused here)")
    args = ap.parse_args()
    out = run(args.arch, smoke=args.smoke, steps=args.steps,
              k_edge=args.k_edge, n_clients=args.clients, batch=args.batch,
              seq=args.seq, ckpt_dir=args.ckpt_dir,
              kernel_mode=args.kernel_mode)
    print(f"done: loss {out['losses'][0]:.4f} -> {out['losses'][-1]:.4f}, "
          f"{out['blocks']} blocks, chain_valid={out['chain_valid']}, "
          f"{out['wall']:.1f}s")


if __name__ == "__main__":
    main()

"""Logical-axis → mesh-axis rules (the GSPMD layer).

Models annotate parameters with *logical* axis names (see
``repro.models.spec``).  This module maps them to mesh axes per runtime
layout, with automatic fallback: a logical axis is sharded only when the
dimension is divisible by the mesh-axis extent and the mesh axis is not
already consumed by another dimension of the same tensor — so GQA archs
with 8 (or 1) KV heads on a 16-way model axis degrade to replicated KV
projections instead of failing to lower.

Layouts
-------
* ``train`` (Layout A, hierarchical FL): every parameter leaf carries two
  leading FL dims ``[n_pods, clients_per_pod, ...]`` — logical axes
  ``fl_pods`` / ``fl_clients`` — sharded over ``pod`` / ``data``.  Inner
  dims use tensor-parallel rules over ``model``.
* ``train_fl1`` (grok-scale): one client per pod; the dead ``fl_clients``
  dim frees the ``data`` axis for FSDP over ``embed``.
* ``serve`` (Layout B): no FL dims; 2D weight sharding (``embed``→data,
  matmul dims→model); activations/caches shard batch over pod+data.
"""
from __future__ import annotations

from typing import Any, Optional, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.dispatch import resolve_kernel_mode  # noqa: F401
from repro.models import ParamSpec
from repro.models.spec import PyTree

# ``resolve_kernel_mode`` is re-exported here on purpose: the launch layer
# resolves WHERE a program runs (mesh placement, the autoscaling specs
# below) and HOW its hot loops execute (kernel-plane backend — compiled
# Pallas on TPU, XLA reference on CPU) side by side, from the same
# runtime facts.  The policy itself lives in ``repro.kernels.dispatch`` so
# the kernel plane stays self-contained.

Axis = Union[str, tuple]        # one candidate: mesh axis or axis tuple
Rule = tuple                    # priority-ordered candidates

# ------------------------------------------------------------------ rules
_TP = {
    "mlp": (("model",),),
    "heads": (("model",),),
    "kv_heads": (("model",),),
    "experts": (("model",),),
    "vocab": (("model",),),
    "layers": (),
    "embed": (),
}

TRAIN_RULES = {
    "fl_pods": (("pod",),),
    "fl_clients": (("data",),),
    "act_batch": (),            # per-client batch stays local
    **_TP,
}

# grok-scale: 1 client per pod -> data axis does FSDP over embed instead
TRAIN_RULES_FL1 = {
    "fl_pods": (("pod",),),
    "fl_clients": (),
    "act_batch": (),
    **{**_TP, "embed": (("data",),)},
}

SERVE_RULES = {
    "fl_pods": (),
    "fl_clients": (),
    "act_batch": (("pod", "data"), ("data",)),
    "kv_seq": (("model",),),    # secondary: only if kv_heads can't use it
    **{**_TP, "embed": (("data",),)},
}

# sweep fabric: the stacked grid-point axis of a batched BHFL sweep
# (repro.fl.sweep).  Prefers the full pod×data product when pods exist,
# otherwise the data axis; the usual divisibility contract applies, so an
# indivisible or single-device bucket degrades to the vmap path instead of
# failing to lower (per shape bucket — each bucket of a plan resolves its
# own spec from its own point count).  Every stacked EngineInputs plane
# rides this one axis — including the latency fabric's per-round
# ``dev_time``/``cons_time`` draws (PR 3), so a consensus-latency×topology
# grid shards its time accounting alongside its training data with no
# extra rules.  The one exception is the seed-major data plane
# (``engine.SHARED_DATA_FIELDS``): train/test/init arrays carry a
# ``[n_seeds]`` seed axis instead of the point axis and are replicated on
# every device (``sweep_data_spec``) — device-resident data scales with
# distinct seeds, not grid points.
SWEEP_RULES = {
    "sweep_points": (("pod", "data"), ("data",)),
}

# logical axes resolved in a second pass, after the primary dims have had
# first pick of the mesh axes (e.g. kv_seq takes "model" only when the
# arch's kv_heads count is not divisible by the model-axis extent)
SECONDARY_AXES = frozenset({"kv_seq"})


def train_rules(clients_per_pod: int) -> dict:
    return TRAIN_RULES_FL1 if clients_per_pod == 1 else TRAIN_RULES


def sweep_spec(n_points: int, mesh: Mesh) -> P:
    """PartitionSpec for a sweep's stacked point axis on ``mesh``.

    ``P()`` (replicated) means the autoscaling contract chose the
    single-device path: the point count does not divide any candidate mesh
    axis, or the mesh has no >1 sweep-capable axis — callers fall back to
    ``vmap`` exactly as ``resolve_spec`` degrades undersized kv heads.
    """
    return resolve_spec((n_points,), ("sweep_points",), SWEEP_RULES, mesh)


def sweep_data_spec() -> P:
    """PartitionSpec for the sweep fabric's seed-major data plane.

    The train/test/init arrays of a sweep are stacked over *distinct
    seeds* (``[n_seeds, ...]``), not grid points, and every point gathers
    its row by ``seed_idx`` inside the engine — so the plane is replicated
    across the mesh (``P()``) rather than sharded with the point axis.
    Kept as a named helper (not a bare ``P()`` at the call site) so the
    data-plane placement contract has exactly one home.
    """
    return P()


# ------------------------------------------------------------- resolution
def _axes_size(mesh: Mesh, cand) -> int:
    return int(np.prod([mesh.shape[a] for a in cand])) if cand else 1


def resolve_spec(shape: tuple, axes: tuple, rules: dict, mesh: Mesh) -> P:
    """Pick mesh axes per dim: first divisible, unused candidate wins.

    Two passes: primary logical axes first, then SECONDARY_AXES claim
    whatever mesh axes remain (kv_seq fallback for undersized kv_heads).
    """
    used: set = set()
    out: list = [None] * len(shape)

    def try_dim(i, dim, name):
        for cand in rules.get(name, ()):
            cand = tuple(a for a in cand if a in mesh.shape)
            if not cand or any(a in used for a in cand):
                continue
            size = _axes_size(mesh, cand)
            if size > 1 and dim % size == 0:
                out[i] = cand if len(cand) > 1 else cand[0]
                used.update(cand)
                return

    for i, (dim, name) in enumerate(zip(shape, axes)):
        if name is not None and name not in SECONDARY_AXES:
            try_dim(i, dim, name)
    for i, (dim, name) in enumerate(zip(shape, axes)):
        if name in SECONDARY_AXES:
            try_dim(i, dim, name)
    # trim trailing Nones (canonical PartitionSpec form)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard_specs(specs: PyTree, rules: dict, mesh: Mesh,
                prefix: tuple[tuple[int, str], ...] = ()) -> PyTree:
    """ParamSpec pytree -> NamedSharding pytree.

    ``prefix``: extra leading (size, logical_name) dims prepended to every
    leaf — the FL client dims of Layout A.
    """
    pshape = tuple(s for s, _ in prefix)
    paxes = tuple(a for _, a in prefix)

    def one(s: ParamSpec) -> NamedSharding:
        return NamedSharding(mesh, resolve_spec(
            pshape + s.shape, paxes + s.axes, rules, mesh))

    return jax.tree.map(one, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def shard_abstract(specs: PyTree, rules: dict, mesh: Mesh,
                   prefix: tuple[tuple[int, str], ...] = (),
                   dtype=None) -> tuple[PyTree, PyTree]:
    """(ShapeDtypeStruct pytree with shardings attached, sharding pytree).

    The FL prefix dims are materialized into the struct shapes.
    """
    pshape = tuple(s for s, _ in prefix)
    shardings = shard_specs(specs, rules, mesh, prefix)

    def one(s: ParamSpec, sh: NamedSharding):
        return jax.ShapeDtypeStruct(pshape + s.shape, dtype or s.dtype,
                                    sharding=sh)

    structs = jax.tree.map(one, specs, shardings,
                           is_leaf=lambda x: isinstance(x, ParamSpec))
    return structs, shardings


def data_sharding(mesh: Mesh, *spec: Any) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def batch_axes(mesh: Mesh) -> tuple:
    """The composite batch axis: ("pod","data") when pods exist."""
    return ("pod", "data") if "pod" in mesh.shape else ("data",)

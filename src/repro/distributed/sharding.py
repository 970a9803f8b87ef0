"""Sharding rules: param/state pytree -> PartitionSpec pytree.

Scheme (DESIGN.md §5):

* float weight matrices ``w [.., out, in]`` — 2D-sharded: ``out -> model``
  and ``in -> data`` (the data axis doubles as an FSDP axis; XLA SPMD
  inserts the all-gathers at use). MoE stacks ``[E, out, in]`` shard
  experts over ``model`` and ``in`` over ``data``.
* packed 1-bit weights ``w_packed [.., out, in/32]`` — ``out -> model``,
  replicated over data: they are 32x smaller, and replicating them is
  what buys the collective-free decode path (the paper's footprint win
  spent on communication).
* embeddings / LM head ``[V, D]`` — vocab over ``model``, D over ``data``.
* norms, biases of tiny fan-out, SSM dynamics, recurrent R — replicated.
* every rule is divisibility-guarded: an axis that does not divide the
  mesh axis is left unsharded (this is what lets one rule set serve
  10 architectures with head counts like 15 and 56).

Leading stack axes (scan periods, per-period layers) are never sharded.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# mesh-axis names used across the project
DATA_AXES = ("pod", "data")      # batch / FSDP axes (pod absent on 1-pod mesh)
MODEL_AXIS = "model"

_REPLICATED_LEAVES = {
    "scale", "bias", "gamma", "beta", "mean", "var", "gn_scale",
    "conv_w", "conv_b", "A_log", "D", "R",
}


def _axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        return int(np.prod([_axis_size(mesh, n) for n in name]))
    return mesh.shape[name] if name in mesh.shape else 1


def _guard(mesh: Mesh, dim: int, axis) -> Optional[Any]:
    """axis if it exists in the mesh and divides dim, else None."""
    if axis is None:
        return None
    if isinstance(axis, tuple):
        axis = tuple(a for a in axis if a in mesh.shape)
        if not axis:
            return None
        axis = axis if len(axis) > 1 else axis[0]
    elif axis not in mesh.shape:
        return None
    return axis if dim % _axis_size(mesh, axis) == 0 else None


# Megatron-style roles: column-parallel projections put the tensor axis
# on their OUT dim (q/k/v/up/gate produce model-sharded features);
# row-parallel projections contract over the model-sharded feature and
# put FSDP on their OUT dim (down/o/out: partial-sum -> one all-reduce /
# reduce-scatter per block instead of re-gathering the wide activation).
_ROW_PARALLEL = {"down_proj", "o_proj", "out_proj"}


def _matrix_spec(mesh: Mesh, shape, *, name: str) -> P:
    """Weight base shape [..., out, in] -> role-dependent spec.

    column-parallel: (model on out, (pod,data)-FSDP on in)
    row-parallel:    ((pod,data)-FSDP on out, model on in)
    MoE stacks [E, out, in]: experts over model, FSDP on in (expert-
    parallel — the contraction stays device-local per expert).
    """
    if len(shape) >= 3:  # stacked experts
        e_ax = _guard(mesh, shape[0], MODEL_AXIS)
        # FSDP on the expert in-dim makes every expert matmul a partial
        # sum -> an all-reduce of the whole [E, cap, d] activation
        # buffer per layer (moonshot hillclimb, §Perf hc7). Only pay
        # that when the expert stack is too big to replicate over data
        # (arctic/jamba); small expert stacks replicate.
        big = float(np.prod(shape)) > 1e9
        in_ax = _guard(mesh, shape[-1], DATA_AXES) if big else None
        return P(e_ax, *(None,) * (len(shape) - 2), in_ax)
    if name in _ROW_PARALLEL:
        return P(_guard(mesh, shape[-2], DATA_AXES),
                 _guard(mesh, shape[-1], MODEL_AXIS))
    return P(_guard(mesh, shape[-2], MODEL_AXIS),
             _guard(mesh, shape[-1], DATA_AXES))


def _path_keys(path) -> list:
    keys = []
    for p in path:
        if hasattr(p, "key"):
            keys.append(p.key)
        elif hasattr(p, "idx"):
            keys.append(p.idx)
        elif hasattr(p, "name"):
            keys.append(p.name)
        else:
            keys.append(str(p))
    return keys


def param_spec(mesh: Mesh, path, leaf) -> P:
    keys = _path_keys(path)
    name = keys[-1] if keys else ""
    parent = next((k for k in reversed(keys[:-1]) if isinstance(k, str)), "")
    shape = tuple(np.shape(leaf))
    # scanned layer stacks carry one leading period/layer axis — never
    # sharded (it is the scan xs dimension)
    stacked = 1 if "layers" in keys and len(shape) >= 1 else 0
    base = shape[stacked:]
    lead = (None,) * stacked

    if name in _REPLICATED_LEAVES or len(base) == 0:
        return P()
    if len(base) == 1:
        if name in ("b", "alpha") and parent not in _ROW_PARALLEL:
            return P(*lead, _guard(mesh, base[0], MODEL_AXIS))
        return P()
    if parent == "router":  # tiny, accuracy-critical — replicate
        return P()
    if name == "w_packed":
        if len(base) >= 3:  # stacked experts [E, out, kw]
            return P(*lead, _guard(mesh, base[0], MODEL_AXIS),
                     *(None,) * (len(base) - 1))
        return P(*lead, _guard(mesh, base[-2], MODEL_AXIS), None)
    if name == "table":  # embedding [V, D]
        return P(_guard(mesh, base[0], MODEL_AXIS),
                 _guard(mesh, base[1], DATA_AXES))
    if name == "w":
        return P(*lead, *_matrix_spec(mesh, base, name=parent))
    return P()


def params_shardings(mesh: Mesh, params) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, param_spec(mesh, path, leaf)),
        params,
    )


# ----------------------------- streaming state -------------------------------


def state_spec(mesh: Mesh, path, leaf) -> P:
    """KV caches [L, B, S, H, Dh]; SSM/xLSTM states [L, B, ...].

    Batch shards over (pod, data) when divisible; for batch-1
    long-context the KV sequence axis shards over model instead; other
    feature axes shard over model when they divide.
    """
    keys = _path_keys(path)
    shape = np.shape(leaf)
    if len(shape) == 0:
        return P()
    name = keys[-1] if keys else ""
    top = keys[0] if keys else ""
    if top == "kv" or name in ("k", "v"):
        # [L, B, S, Hkv, Dh]
        b_ax = _guard(mesh, shape[1], DATA_AXES)
        if b_ax is None:
            b_ax = _guard(mesh, shape[1], "data")
        s_ax = _guard(mesh, shape[2], MODEL_AXIS)
        return P(None, b_ax, s_ax, None, None)
    if top == "memory" or name == "memory":
        # encoder memory [B, S, D]
        return P(_guard(mesh, shape[0], DATA_AXES),
                 _guard(mesh, shape[1], MODEL_AXIS), None)
    if len(shape) >= 2:
        b_ax = _guard(mesh, shape[1], DATA_AXES) or _guard(mesh, shape[1], "data")
        rest = [None] * (len(shape) - 2)
        # shard the largest divisible feature axis over model
        cands = [
            (shape[i], i) for i in range(2, len(shape))
            if _guard(mesh, shape[i], MODEL_AXIS) is not None
        ]
        if cands:
            _, i = max(cands)
            rest[i - 2] = MODEL_AXIS
        return P(None, b_ax, *rest)
    return P()


def state_shardings(mesh: Mesh, state) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, state_spec(mesh, path, leaf)),
        state,
    )


# ------------------------------- batches -------------------------------------


def batch_spec(mesh: Mesh, path, leaf) -> P:
    shape = np.shape(leaf)
    if len(shape) == 0:
        return P()
    b_ax = _guard(mesh, shape[0], DATA_AXES) or _guard(mesh, shape[0], "data")
    return P(b_ax, *(None,) * (len(shape) - 1))


def batch_shardings(mesh: Mesh, batch) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, batch_spec(mesh, path, leaf)),
        batch,
    )


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# --------------------------- BNN serving (DESIGN.md §10) ----------------------
#
# The packed-BNN serving path is pure data parallelism over a 1-D
# ``("data",)`` mesh (``launch.mesh.make_serving_mesh``): packed weights
# replicated (they are ~1.75 MB — the paper's 32x footprint win spent on
# a collective-free forward), batch sharded. These helpers are what
# ``core.bnn.bnn_serve_fn(mesh=...)`` builds its shard_map specs from.


def mesh_devices(mesh: Optional[Mesh]) -> int:
    """Device count of a serving mesh (1 for ``None`` — the
    single-device dispatch path)."""
    return 1 if mesh is None else int(np.prod(list(mesh.shape.values())))


def serve_specs(mesh: Mesh) -> tuple[P, P, P]:
    """``(params_spec, images_spec, logits_spec)`` for the serving
    forward: weights replicated, batch dim sharded over ``data``.

    Reuses the rule-table guard discipline: a mesh without a ``data``
    axis degrades to fully replicated specs (single-device dispatch)
    instead of erroring — the same ``_guard`` posture that lets one
    rule set serve every mesh shape. Batch divisibility is NOT guarded
    here (shard_map specs are shape-free); the serving executors are
    responsible for dispatching only device-divisible batches
    (``serve.executor.extent_for(..., devices=)`` /
    ``serve.buckets.mesh_buckets``), padding bit-neutral zero rows when
    a batch does not divide.
    """
    axis = "data" if "data" in mesh.shape else None
    return P(), P(axis), P(axis)


def shard_serving(apply_fn, mesh: Mesh):
    """Wrap a serving forward ``(packed, images) -> logits`` in
    ``shard_map`` over ``mesh`` with :func:`serve_specs`: weights
    replicated, batch and logits sharded over ``data``.

    ``check_vma=False``: the Pallas kernel calls inside the per-shard
    program carry no replication rules; correctness rests on the
    per-sample independence of the forward, asserted bit-exactly in the
    sharded test matrix (``tests/test_sharded_serve.py``).
    """
    p_spec, x_spec, y_spec = serve_specs(mesh)
    return jax.shard_map(apply_fn, mesh=mesh, in_specs=(p_spec, x_spec),
                         out_specs=y_spec, check_vma=False)


# ------------------------- activation constraints -----------------------------
#
# Models are mesh-agnostic; the launcher installs the active mesh here and
# model code calls ``constrain(x, batch_axes, seq_axis, ...)`` at layer
# boundaries (Megatron-style sequence parallelism: the residual stream
# lives [B/(pod*data), S/model, D] between blocks). Every axis is
# divisibility-guarded, so the same call is a no-op on a single CPU
# device (smoke tests) and for shapes that don't divide (decode S=1).

import contextlib
import threading

_ACTIVE = threading.local()


def get_active_mesh() -> Optional[Mesh]:
    return getattr(_ACTIVE, "mesh", None)


@contextlib.contextmanager
def activation_mesh(mesh: Optional[Mesh]):
    prev = get_active_mesh()
    _ACTIVE.mesh = mesh
    try:
        yield
    finally:
        _ACTIVE.mesh = prev


def constrain(x, *axes):
    """with_sharding_constraint under the installed mesh, guarded.

    ``axes`` entries are mesh-axis names / tuples / None, one per dim.
    """
    mesh = get_active_mesh()
    if mesh is None:
        return x
    spec = P(*(
        _guard(mesh, dim, ax) for dim, ax in zip(x.shape, axes)
    ))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_seq(x):
    """Residual stream [B, S, D] between blocks: batch over (pod, data),
    seq/features replicated over model (classic Megatron TP layout — the
    model axis parallelism lives inside the blocks via the col/row
    weight rules; sequence-sharding the residual was tried and measured
    WORSE under XLA SPMD: the chunked-attention q-slices fight the
    seq shard and trigger involuntary remat, see EXPERIMENTS.md §Perf)."""
    return constrain(x, DATA_AXES, None, None)

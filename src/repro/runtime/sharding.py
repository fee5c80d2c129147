"""Mesh-aware sharding rules (DESIGN.md §5).

Parameters carry their own axis preferences (models.lm.params.ParamMeta);
this module resolves *activation* and *input* shardings:

  * batch over ("pod", "data") when divisible, falling back to "data",
    then to replication (long_500k batch=1);
  * when the batch cannot use an axis, long sequences pick it up instead
    (sequence sharding — the LM analogue of the paper's §IV.B row-wise
    image segmentation);
  * logits/activations constrained so the vocab-TP lm_head output stays
    sharded over "model";
  * FCN serving activations (NHWC image planes and the score/link/label
    maps derived from them): batch over "data" for data-parallel plans,
    rows over "model" for row-band plans, or BOTH AT ONCE for the 2-D
    GridPlan (batch_axis="data" + rows_axis="model" compose into one
    P("data", "model", ...) layout) — fcn_activation_specs is consumed
    by runtime.executor's ExecutionPlans; fcn_batch_axis is the
    divisibility rule for callers picking a batch axis themselves.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_seq_spec(
    mesh: Mesh, batch: int, seq: Optional[int] = None
) -> P:
    """Spec for (batch, seq, ...) inputs: shard batch as much as divisible,
    give leftover data-parallel capacity to the sequence axis."""
    sizes = mesh_axis_sizes(mesh)
    batch_axes = []
    seq_axes = []
    remaining = batch
    for ax in ("pod", "data"):
        if ax not in sizes:
            continue
        if remaining % sizes[ax] == 0 and remaining >= sizes[ax]:
            batch_axes.append(ax)
            remaining //= sizes[ax]
        elif seq is not None and seq % sizes[ax] == 0:
            seq_axes.append(ax)
    b = tuple(batch_axes) if batch_axes else None
    s = tuple(seq_axes) if seq_axes else None
    if seq is None:
        return P(b if b is None or len(batch_axes) > 1 else batch_axes[0])
    return P(
        b if b is None or len(batch_axes) > 1 else batch_axes[0],
        s if s is None or len(seq_axes) > 1 else seq_axes[0],
    )


def input_shardings(
    mesh: Mesh, specs: Dict[str, jax.ShapeDtypeStruct]
) -> Dict[str, NamedSharding]:
    """NamedShardings for the input_specs() dict of a shape cell."""
    out = {}
    for name, sds in specs.items():
        if sds.ndim == 0:
            out[name] = NamedSharding(mesh, P())
        elif sds.ndim == 1:
            out[name] = NamedSharding(
                mesh, batch_seq_spec(mesh, sds.shape[0])
            )
        else:
            spec = batch_seq_spec(mesh, sds.shape[0], sds.shape[1])
            # pad spec with None for trailing dims
            out[name] = NamedSharding(mesh, spec)
    return out


def logits_spec(mesh: Mesh, batch: int, seq: int) -> P:
    bs = batch_seq_spec(mesh, batch, seq)
    parts = list(bs) + [None] * (3 - len(bs))
    sizes = mesh_axis_sizes(mesh)
    if "model" in sizes:
        parts[2] = "model"
    return P(*parts)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def fcn_batch_axis(mesh: Mesh, batch: int, axis: str = "data") -> Optional[str]:
    """The mesh axis an FCN batch can shard over, or None (replicate)."""
    n = mesh_axis_sizes(mesh).get(axis, 1)
    return axis if n > 1 and batch % n == 0 else None


def fcn_activation_specs(
    batch_axis: Optional[str] = None, rows_axis: Optional[str] = None
) -> Dict[str, P]:
    """PartitionSpecs for the FCN serving activations.

    NHWC inputs and the 1/4-scale maps share one layout decision: the
    batch dim over ``batch_axis`` (data-parallel plans, paper's batch
    level) and/or the row dim over ``rows_axis`` (row-band plans, paper
    §IV.B).  Keys: "image" (N,H,W,C), "score" (N,h,w), "links"
    (N,h,w,8), "labels" (N,h,w).
    """
    return {
        "image": P(batch_axis, rows_axis, None, None),
        "score": P(batch_axis, rows_axis, None),
        "links": P(batch_axis, rows_axis, None, None),
        "labels": P(batch_axis, rows_axis, None),
    }


def activation_constrainer(mesh: Mesh, global_batch: int,
                           seq_shard: bool = False):
    """Returns shard(x, kind) applying with_sharding_constraint to
    activations so SPMD propagation cannot silently replicate them (the
    18 GiB/layer lesson from the first tinyllama dry-run — EXPERIMENTS.md
    §Perf).

    kinds:
      "bld"      (B, L, D)     batch over pod/data axes
      "blhd"     (B, L, H, hd) + heads over "model" when divisible
      "ecd"      (E, cap, D)   experts over "model" when divisible
      "boundary" (B, L, D)     the residual stream between blocks; with
                 ``seq_shard`` it is L-sharded over "model" (Megatron-SP
                 style) so remat-saved activations shrink by the TP degree
                 — the §Perf memory-term lever for train cells
    """
    sizes = mesh_axis_sizes(mesh)
    batch_axes = []
    rem = global_batch
    for ax in ("pod", "data"):
        if ax in sizes and rem % sizes[ax] == 0 and rem >= sizes[ax]:
            batch_axes.append(ax)
            rem //= sizes[ax]
    b = tuple(batch_axes) if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None
    )
    model_n = sizes.get("model", 1)

    def shard(x, kind: str):
        if kind == "bld":
            spec = P(b, None, None)
        elif kind == "boundary":
            l_ok = (seq_shard and x.shape[1] % model_n == 0
                    and x.shape[1] >= model_n)
            spec = P(b, "model" if l_ok else None, None)
        elif kind == "blhd":
            h_ok = x.shape[2] % model_n == 0 and x.shape[2] >= model_n
            spec = P(b, None, "model" if h_ok else None, None)
        elif kind == "ecd":
            e_ok = x.shape[0] % model_n == 0 and x.shape[0] >= model_n
            spec = P("model" if e_ok else None, None, None)
        else:
            raise ValueError(kind)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec)
        )

    return shard

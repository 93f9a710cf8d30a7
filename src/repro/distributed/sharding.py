"""Logical-axis sharding rules: the paper's Spatial-Map directives bound to
mesh axes (DESIGN.md §5).

Every model parameter/activation declares *logical* axis names
(``models/common.Axes``); this module maps them onto the physical mesh:

  Spatial Map(batch  -> pod, data)     — DP (the image-fold streaming axis)
  Spatial Map(heads/mlp/vocab/experts -> model) — TP/EP (the filter-fold
                                          stationary axis: weights never move)
  Temporal Map(seq)                    — streamed in time, unsharded
                                          (sequence-sharded variants opt-in)

``constrain`` applies activation sharding constraints only when a
(mesh, rules) context has been installed by a launcher — model code stays
runnable on a single CPU device with zero mesh machinery.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.models.common import Axes

__all__ = ["ShardingRules", "make_rules", "spec_for", "tree_shardings",
           "set_context", "clear_context", "constrain", "zero1_shardings",
           "vision_shardings", "vision_batch_sharding", "FoldConvShards",
           "fold_conv_shards"]

MeshAxes = Optional[Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> mesh axis (or tuple of axes, or None)."""
    table: Dict[str, Any]
    seq_shard_kv: bool = False   # long-context decode: shard cache seq on dp

    def get(self, name: Optional[str]):
        if name is None:
            return None
        return self.table.get(name)


def _dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def make_rules(cfg, mesh: Mesh, *, seq_shard_kv: bool = False,
               shard_batch: bool = True) -> ShardingRules:
    """Derive the rule table from config divisibilities and mesh geometry."""
    model = mesh.shape.get("model", 1)
    dp = _dp_axes(mesh)
    # head params are padded to head_pad_multiple for even TP (qwen2.5:
    # 40 -> 48); divisibility must be checked on the PADDED count
    heads_ok = cfg.padded_heads % model == 0
    kv_ok = cfg.kv_heads % model == 0
    d_in = cfg.ssm_expand * cfg.d_model
    table = {
        Axes.BATCH: dp if shard_batch else None,
        Axes.VOCAB: "model",
        Axes.HEADS: "model" if heads_ok else None,
        Axes.KV_HEADS: "model" if kv_ok else None,   # else replicated (GQA)
        Axes.MLP: "model",
        Axes.EXPERTS: "model",
        Axes.EXPERT_MLP: None,
        Axes.EMBED: None,
        Axes.SSM_INNER: "model" if d_in % model == 0 else None,
        Axes.STATE: None,
        Axes.CONV_K: None,
        Axes.HEAD_DIM: None,
        Axes.LAYERS: None,
        Axes.SEQ: None,
        "seq_kv": dp if seq_shard_kv else None,
        "cache_kv": "model" if cfg.cache_kv_heads % model == 0 else None,
    }
    return ShardingRules(table=table, seq_shard_kv=seq_shard_kv)


def spec_for(axes: Sequence[Optional[str]], rules: ShardingRules
             ) -> PartitionSpec:
    return PartitionSpec(*[rules.get(a) for a in axes])


def tree_shardings(axes_tree, rules: ShardingRules, mesh: Mesh):
    """Map an axes tree (tuples of logical names) to NamedShardings."""
    return jax.tree.map(
        lambda a: NamedSharding(mesh, spec_for(a, rules)),
        axes_tree, is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------------------
# Vision serving: the conv-trunk binding of the paper's Spatial Maps
# ---------------------------------------------------------------------------

def vision_batch_sharding(mesh: Mesh, plan) -> NamedSharding:
    """NamedSharding for an NCHW activation batch under a serving
    ``MappingPlan`` (``core/mapping.py:serving_conv_plan``): the batch —
    the image-fold streaming axis — shards across the plan's data axis."""
    return NamedSharding(mesh, plan.partition_spec(("N", None, None, None)))


def _mesh_axis_sizes(mesh: Mesh, plan) -> Tuple[str, int, Optional[str], int]:
    """(data axis, its size, model axis, its size) of a serving plan."""
    by_dim = {d.dim: d.axis for d in plan.spatial()}
    data_axis, model_axis = by_dim.get("N"), by_dim.get("N_F")
    data = mesh.shape.get(data_axis, 1) if data_axis else 1
    model = mesh.shape.get(model_axis, 1) if model_axis else 1
    return data_axis, data, model_axis, model


def vision_shardings(params, mesh: Mesh, plan):
    """NamedShardings for a conv-trunk param tree under a serving plan.

    Conv layers (4-D ``w`` OIHW + its ``b``) shard on the N_F filter-fold
    axis — the stationary axis: each model-parallel device holds its slice
    of every filter fold and the weights never move at serving time.  A
    layer whose filter count does not divide the model-axis size
    replicates (same fallback discipline as ``make_rules``), as does
    everything that is not a conv layer (the fc head).
    """
    model = _mesh_axis_sizes(mesh, plan)[3]
    w_spec = plan.partition_spec(("N_F", None, None, None))
    b_spec = plan.partition_spec(("N_F",))
    replicate = NamedSharding(mesh, PartitionSpec())

    def is_conv(leaf) -> bool:
        return (isinstance(leaf, dict) and "w" in leaf
                and getattr(leaf["w"], "ndim", 0) == 4
                and leaf["w"].shape[0] % model == 0)

    out = {}
    for name, leaf in params.items():
        if is_conv(leaf):
            out[name] = {k: NamedSharding(mesh, w_spec) if k == "w"
                         else NamedSharding(mesh, b_spec)
                         for k in leaf}
        else:
            out[name] = jax.tree.map(lambda _: replicate, leaf)
    return out


@dataclasses.dataclass(frozen=True)
class FoldConvShards:
    """One fold conv under ``shard_map`` on a serving mesh: the per-device
    nest (batch ``n``, filters ``nf``, input channels ``c``, ``groups``)
    and the PartitionSpecs of its operands.

    A Mosaic kernel cannot be partitioned by GSPMD, so each device runs
    the kernel on its own shard: the batch split over the data axis and,
    where the weights are split (``vision_shardings``' rule: N_F divides
    the model axis), the filters over the model axis.  Dense convs read
    every input channel (the activation is gathered over the model axis
    first); grouped convs whose group count divides the model axis read
    only their groups' channels."""
    n: int
    nf: int
    c: int
    groups: int
    x: PartitionSpec
    w: PartitionSpec
    vec: PartitionSpec
    out: PartitionSpec

    def wrap(self, conv, mesh: Mesh):
        """``conv(x, w, b, scale, shift, residual)`` run per shard; absent
        (None) operands stay absent.  The residual splits like the
        output."""
        specs = (self.x, self.w, self.vec, self.vec, self.vec, self.out)

        def run(*args):
            live = [i for i, a in enumerate(args) if a is not None]

            def local(*vals):
                full = [None] * len(args)
                for i, v in zip(live, vals):
                    full[i] = v
                return conv(*full)
            return jax.shard_map(
                local, mesh=mesh, in_specs=tuple(specs[i] for i in live),
                out_specs=self.out, check_vma=False
            )(*(args[i] for i in live))
        return run


def fold_conv_shards(mesh: Mesh, plan, *, n: int, nf: int, c: int,
                     groups: int) -> FoldConvShards:
    """The per-device geometry of an (N, C) -> N_F conv with ``groups``
    under a serving plan (``core/mapping.py:serving_conv_plan``)."""
    _, data, _, model = _mesh_axis_sizes(mesh, plan)
    if n % data:
        raise ValueError(f"batch {n} does not split over a data axis of "
                         f"{data} devices")
    # a grouped conv splits only whole groups: each device's filters must
    # read channels that live on that device
    split = (model > 1 and nf % model == 0
             and (groups == 1 or groups % model == 0))
    m = model if split else 1
    split_c = split and groups > 1
    nf_axis = "N_F" if split else None
    return FoldConvShards(
        n=n // data, nf=nf // m, c=c // m if split_c else c,
        groups=groups // m if split_c else groups,
        x=plan.partition_spec(("N", nf_axis if split_c else None, None,
                               None)),
        w=plan.partition_spec((nf_axis, None, None, None)),
        vec=plan.partition_spec((nf_axis,)),
        out=plan.partition_spec(("N", nf_axis, None, None)))


# ---------------------------------------------------------------------------
# ZeRO-1: optimizer-state sharding over the data axes
# ---------------------------------------------------------------------------

def zero1_shardings(axes_tree, shapes_tree, rules: ShardingRules, mesh: Mesh):
    """Optimizer moments/master: param sharding + the DP axes folded onto the
    first dimension that is unsharded and divisible (classic ZeRO-1)."""
    dp = _dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1

    def one(axes, shape):
        spec = list(spec_for(axes, rules))
        if dp and dp_size > 1:
            for i, (s, dim) in enumerate(zip(spec, shape)):
                if s is None and dim % dp_size == 0 and dim > 0:
                    spec[i] = dp
                    break
        return NamedSharding(mesh, PartitionSpec(*spec))

    return jax.tree.map(
        lambda a, sh: one(a, tuple(sh.shape)),
        axes_tree, shapes_tree, is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------------------
# activation-constraint context (installed by launchers)
# ---------------------------------------------------------------------------

_CTX: Optional[Tuple[Mesh, ShardingRules]] = None


def set_context(mesh: Mesh, rules: ShardingRules) -> None:
    global _CTX
    _CTX = (mesh, rules)


def clear_context() -> None:
    global _CTX
    _CTX = None


def constrain(x, logical_names: Sequence[Optional[str]]):
    """Sharding constraint on an activation; no-op without a context."""
    if _CTX is None:
        return x
    mesh, rules = _CTX
    spec = spec_for(logical_names, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

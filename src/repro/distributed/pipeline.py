"""Pipeline parallelism over the `pod` axis (GPipe fill-drain schedule).

At 1000+ nodes the cross-pod (DCN) links are too slow for TP collectives;
the standard posture is PP across pods: each pod holds a contiguous stage
of layers and only stage-boundary activations cross the slow links
(microbatched to hide the bubble).

Implementation: ``shard_map`` over the ``stage`` mesh axis; each stage owns
``n_layers / n_stages`` of the stacked block parameters; activations move
stage->stage+1 with ``lax.ppermute``. The schedule below is GPipe
(fill-drain): T = n_micro + n_stages - 1 ticks, bubble fraction
(n_stages-1)/T. Within a stage, the usual data/model sharding applies
unchanged (the paper's directive algebra composes: PP is a Temporal Map
over the stage axis).

The functional core (`pipeline_spmd_fn`) is exact w.r.t. the unpiped
forward (tested single-device with n_stages=1..4 emulated sequentially);
the mesh path compiles in the multi-pod dry-run (--pp).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["gpipe_schedule", "pipeline_apply", "split_stages"]


def split_stages(stacked_params, n_stages: int):
    """Split a layer-stacked param tree into n_stages contiguous chunks,
    re-stacked on a leading stage axis: (L, ...) -> (S, L/S, ...)."""
    def one(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])
    return jax.tree.map(one, stacked_params)


def gpipe_schedule(n_micro: int, n_stages: int):
    """(tick, stage) -> microbatch index processed (or -1 = bubble)."""
    ticks = n_micro + n_stages - 1
    return [[t - s if 0 <= t - s < n_micro else -1
             for s in range(n_stages)] for t in range(ticks)]


def pipeline_apply(stage_fn: Callable, stage_params, x_micro: jnp.ndarray,
                   *, n_stages: int, axis_name: str = "pod"):
    """Run the GPipe schedule inside shard_map over ``axis_name``.

    stage_fn(params_slice, act) -> act : applies one stage's layers.
    stage_params : per-device slice (leading stage axis removed by
        shard_map's in_spec).
    x_micro : (n_micro, mb, T, D) input activations — only stage 0 reads
        them; other stages receive from the left neighbour.

    Returns (n_micro, mb, T, D) outputs valid on the LAST stage (callers
    psum/select as needed).
    """
    n_micro = x_micro.shape[0]
    stage = jax.lax.axis_index(axis_name)
    ticks = n_micro + n_stages - 1
    act_shape = x_micro.shape[1:]

    def tick_body(carry, t):
        act_in, outs = carry
        mb_idx = t - stage                       # microbatch at this stage
        valid = (mb_idx >= 0) & (mb_idx < n_micro)
        # stage 0 pulls its microbatch from x_micro; others use received
        src = jnp.where(
            stage == 0,
            x_micro[jnp.clip(mb_idx, 0, n_micro - 1)],
            act_in)
        out = stage_fn(stage_params, src)
        out = jnp.where(valid, out, jnp.zeros_like(out))
        # pass to the right neighbour (ring permute; last->first discarded)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        nxt = jax.lax.ppermute(out, axis_name, perm)
        # last stage records finished microbatches
        done = valid & (stage == n_stages - 1)
        outs = jax.lax.cond(
            done,
            lambda o: o.at[jnp.clip(mb_idx, 0, n_micro - 1)].set(out),
            lambda o: o, outs)
        return (nxt, outs), None

    outs0 = jnp.zeros((n_micro,) + act_shape, x_micro.dtype)
    (last, outs), _ = jax.lax.scan(
        tick_body, (jnp.zeros(act_shape, x_micro.dtype), outs0),
        jnp.arange(ticks))
    # only the last stage wrote outputs; psum replicates them to all
    # stages so the caller sees one coherent result
    return jax.lax.psum(outs, axis_name)


def make_pipelined_stack(cfg, layer_fn: Callable, *, n_stages: int,
                         mesh: Optional[Mesh] = None,
                         axis_name: str = "pod"):
    """Build a pipelined version of a homogeneous layer stack.

    layer_fn(lp, x) -> x : one layer (the scan body used by the model).
    Returns run(stacked_params, x_micro) usable two ways:
      * mesh=None  — sequential emulation (exactness tests);
      * mesh given — shard_map over ``axis_name`` (the multi-pod path).
    """
    def stage_fn(params_slice, act):
        def body(x, lp):
            return layer_fn(lp, x), None
        out, _ = jax.lax.scan(body, act, params_slice)
        return out

    if mesh is None:
        def run_seq(stacked_params, x_micro):
            staged = split_stages(stacked_params, n_stages)
            outs = []
            for m in range(x_micro.shape[0]):
                act = x_micro[m]
                for s in range(n_stages):
                    act = stage_fn(jax.tree.map(lambda a: a[s], staged),
                                   act)
                outs.append(act)
            return jnp.stack(outs)
        return run_seq

    def spmd(staged_local, xm):
        # shard_map leaves a size-1 stage axis on the local param shard
        sp = jax.tree.map(lambda a: a[0], staged_local)
        return pipeline_apply(stage_fn, sp, xm, n_stages=n_stages,
                              axis_name=axis_name)

    def run_mesh(stacked_params, x_micro):
        staged = split_stages(stacked_params, n_stages)
        pspecs = jax.tree.map(lambda _: P(axis_name), staged)
        fn = jax.shard_map(spmd, mesh=mesh, in_specs=(pspecs, P()),
                           out_specs=P(), check_vma=False)
        return fn(staged, x_micro)
    return run_mesh

"""GPipe-style pipeline parallelism over a 'stage' mesh axis (shard_map).

An alternative to the TP/FSDP layout for depth-dominated models: layers are
split into S stages (params stage-sharded); microbatches stream through the
pipeline, stage boundaries move activations with collective_permute.  The
schedule runs M + S - 1 ticks (classic GPipe bubble = (S-1)/(M+S-1)).

This is deliberately self-contained — select with ``parallelism='pp'`` in a
launcher or use ``pipeline_apply`` directly; the dry-run exercises it via
tests/test_pipeline.py on a host-device mesh.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


__all__ = ["pipeline_apply", "bubble_fraction"]


def bubble_fraction(num_stages: int, num_micro: int) -> float:
    return (num_stages - 1) / (num_micro + num_stages - 1)


def pipeline_apply(
    stage_fn: Callable,          # (stage_params, x (Bm, ...)) -> (Bm, ...)
    stage_params,                # pytree, leading dim = num_stages
    x_micro: jax.Array,          # (M, Bm, ...) microbatches
    mesh: Mesh,
    stage_axis: str = "stage",
):
    """Run M microbatches through S pipeline stages; returns (M, Bm, ...).

    stage_params leading dim is sharded over `stage_axis`; every device
    executes its stage each tick (bubbles compute garbage that is never
    read — standard GPipe).
    """
    S = mesh.shape[stage_axis]
    M = x_micro.shape[0]
    T = M + S - 1

    def spmd(params_local, xs):
        # params_local: (1, ...) slice; xs: full (M, Bm, ...) (replicated)
        params_one = jax.tree.map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(stage_axis)
        # mark carries as stage-varying up front (scan requires stable vma)
        buf = jax.lax.pcast(jnp.zeros_like(xs[0]), stage_axis, to="varying")
        outs = jax.lax.pcast(jnp.zeros_like(xs), stage_axis, to="varying")

        def tick(carry, t):
            buf, outs = carry
            # receive boundary activation from the previous stage
            recv = jax.lax.ppermute(
                buf, stage_axis, [(i, (i + 1) % S) for i in range(S)])
            inject = jnp.where(t < M, t, 0)
            x_in = jnp.where(sid == 0, xs[inject], recv)
            y = stage_fn(params_one, x_in)
            # last stage records finished microbatch t - (S - 1)
            # (masked write — lax.cond branches disagree on shard_map
            # varying axes, a masked select does not)
            slot = t - (S - 1)
            slot_c = jnp.clip(slot, 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, slot_c, 0,
                                               keepdims=False)
            val = jnp.where((sid == S - 1) & (slot >= 0), y, cur)
            outs = jax.lax.dynamic_update_index_in_dim(outs, val, slot_c, 0)
            return (y, outs), None

        (buf, outs), _ = jax.lax.scan(
            tick, (buf, outs), jnp.arange(T))
        # broadcast results from the last stage to all (psum of masked)
        outs = jnp.where(sid == S - 1, outs, 0)
        return jax.lax.psum(outs, stage_axis)

    params_spec = jax.tree.map(lambda _: P(stage_axis), stage_params)
    fn = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(params_spec, P()),
        out_specs=P(),
    )
    return fn(stage_params, x_micro)

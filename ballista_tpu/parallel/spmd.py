"""SPMD stage programs: distributed aggregation and shuffle over a Mesh.

The reference's two distributed primitives map to in-program collectives
(SURVEY §2.8):

- partial/final aggregation (HashAggregateExec split + shuffle,
  reference rust/scheduler/src/planner.rs:149-171):
  per-shard masked segment-sum partials, merged with lax.psum over ICI —
  no materialize-then-fetch.
- repartition exchange (ShuffleWriter -> Flight fetch -> ShuffleReader,
  reference rust/executor/src/flight_service.rs:104-126 +
  rust/core/src/execution_plans/shuffle_reader.rs:77-99):
  rows bucketed by key ownership and exchanged with lax.all_to_all, then
  aggregated locally on the owning shard.

Programs are built once per (shapes, mesh) and jit-cached by XLA.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Sequence, Tuple


def build_psum_aggregate(mesh, num_groups: int,
                         mask_fn: Callable, value_fns: Sequence[Callable]):
    """Aggregation with replicated output: each shard computes masked
    per-group partial sums from its rows; lax.psum merges over the mesh.

    Inputs to the returned fn: per-column arrays sharded on axis 'data'
    (row dimension), plus a codes array (group id per row, also sharded).
    Returns [1 + n_values, num_groups]: row 0 = counts, then one row per
    value expression. Replicated on all shards.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def per_shard(codes, *cols):
        mask = mask_fn(*cols)
        maskf = mask.astype(jnp.float32)
        safe = jnp.where(mask, codes, num_groups)  # dump slot
        outs = [jax.ops.segment_sum(maskf, safe, num_segments=num_groups + 1)]
        for vf in value_fns:
            v = vf(*cols).astype(jnp.float32)
            outs.append(
                jax.ops.segment_sum(v * maskf, safe, num_segments=num_groups + 1)
            )
        stacked = jnp.stack(outs)[:, :num_groups]  # drop dump slot
        return jax.lax.psum(stacked, "data")

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P("data"),) + tuple(P("data") for _ in range(n_values_in(value_fns, mask_fn))),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


def n_values_in(value_fns, mask_fn) -> int:
    """Number of column inputs — taken from fn arity (they all share the
    same positional column tuple)."""
    import inspect

    return len(inspect.signature(mask_fn).parameters)


def build_all_to_all_exchange_aggregate(mesh, axis: str = "data"):
    """Shuffle-by-key aggregation: each shard buckets its rows by owning
    shard (key % n_dev), exchanges buckets with lax.all_to_all, and the
    owner aggregates its received rows with a local segment-sum.

    Returns fn(keys[data-sharded], values[data-sharded], groups_per_shard)
    -> (owned_sums [n_dev * groups_per_shard] replicated-by-concat layout:
    each shard's slice holds sums for keys with key % n_dev == shard and
    key // n_dev < groups_per_shard).
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n_dev = mesh.shape[axis]

    def per_shard(keys, values, groups_per_shard: int):
        s = keys.shape[0]
        tgt = jnp.mod(keys, n_dev).astype(jnp.int32)
        order = jnp.argsort(tgt)
        keys_s = keys[order]
        vals_s = values[order]
        tgt_s = tgt[order]
        onehot = jax.nn.one_hot(tgt_s, n_dev, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - onehot)
        pos = jnp.take_along_axis(pos, tgt_s[:, None], axis=1)[:, 0]
        # fixed-capacity buckets (worst case: all rows to one target)
        bk = jnp.full((n_dev, s), -1, dtype=keys.dtype)
        bv = jnp.zeros((n_dev, s), dtype=values.dtype)
        bk = bk.at[tgt_s, pos].set(keys_s)
        bv = bv.at[tgt_s, pos].set(vals_s)
        # the exchange: shard i sends bucket j to shard j
        rk = jax.lax.all_to_all(bk, axis, split_axis=0, concat_axis=0, tiled=True)
        rv = jax.lax.all_to_all(bv, axis, split_axis=0, concat_axis=0, tiled=True)
        rk = rk.reshape(-1)
        rv = rv.reshape(-1)
        valid = rk >= 0
        local_group = jnp.where(valid, rk // n_dev, groups_per_shard)
        sums = jax.ops.segment_sum(
            jnp.where(valid, rv, 0.0), local_group, num_segments=groups_per_shard + 1
        )
        return sums[:groups_per_shard]

    def wrapped(keys, values, groups_per_shard: int):
        f = shard_map(
            functools.partial(per_shard, groups_per_shard=groups_per_shard),
            mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=P(axis),
            check_vma=False,
        )
        return f(keys, values)

    return jax.jit(wrapped, static_argnums=(2,))


def build_q1_style_step(mesh, num_groups: int, cutoff_days: int):
    """The flagship distributed stage: TPC-H q1's pipeline as one SPMD
    program — filter mask, four derived measures, masked per-group partials,
    psum over ICI. Column layout: (codes, qty, price, disc, tax, shipdate)."""
    import jax.numpy as jnp

    def mask_fn(qty, price, disc, tax, ship):
        return ship <= cutoff_days

    value_fns = [
        lambda qty, price, disc, tax, ship: qty,
        lambda qty, price, disc, tax, ship: price,
        lambda qty, price, disc, tax, ship: price * (1.0 - disc),
        lambda qty, price, disc, tax, ship: price * (1.0 - disc) * (1.0 + tax),
        lambda qty, price, disc, tax, ship: disc,
    ]
    return build_psum_aggregate(mesh, num_groups, mask_fn, value_fns)

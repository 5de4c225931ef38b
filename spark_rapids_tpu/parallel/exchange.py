"""ICI collective exchange: hash-partition shuffle as one XLA all_to_all.

Replaces the reference's UCX transport (UCX.scala:71, RapidsShuffleClient/
Server) for stage-resident execution: every device bucketizes its rows by
destination (hash(key) % n_devices) into fixed-capacity send buckets, one
``lax.all_to_all`` swaps the bucket axis across the mesh over ICI, and each
device re-reduces what it received.  Static shapes throughout: bucket
capacity is a compile-time constant; overflow is *detected* (returned as a
per-device scalar) so callers can split-and-retry with a bigger bucket — the
same contract as the join/aggregation OOM-retry loops.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops import groupby

Value = Tuple[jax.Array, Optional[jax.Array]]


def hash_ids(keys: Sequence[Value], n_parts: int) -> jax.Array:
    """Partition id per row: Spark-exact pmod(murmur3(keys, 42), n)."""
    from ..ops.hashing import spark_partition_id
    return spark_partition_id(keys, n_parts)


def bucketize(pids: jax.Array, active: jax.Array, n_parts: int,
              bucket_cap: int, arrays: Sequence[jax.Array],
              live_cap: Optional[int] = None):
    """Scatter rows into [n_parts, bucket_cap] send buckets.

    Returns (bucketed arrays, per-bucket counts, overflow scalar).  Rows
    beyond a bucket's capacity are dropped and counted in ``overflow`` —
    callers must check it is zero (and retry with larger buckets otherwise).

    A scatter is paid by SOURCE row, live or not, and a 64-bit one dearly
    (``ops/batch_utils._SCATTER64_ROW_NS`` against ``_SCATTER_ROW_NS``; the
    gathers ``_GATHER64_NS`` / ``_GATHER_NS`` an element): a few thousand
    live rows of a 2M-slot capacity cost what 2M rows cost.  A caller
    that knows a static bound on its live rows (the mesh exchange does: the
    host read the count a step earlier) passes it as ``live_cap``: the one
    sort over every row puts the inactive ones last, and only the first
    ``live_cap`` sorted rows are gathered, placed and scattered.  At most
    ``live_cap`` rows may be active; without it every row is walked.
    """
    capacity = pids.shape[0]
    pid_sortable = jnp.where(active, pids, n_parts)  # inactive rows last
    # a bucket's rows in any order (an unstable sort compiles in 15 s for
    # the TPU, a stable one in 39 s at 1M rows)
    perm = jnp.argsort(pid_sortable, stable=False)
    if live_cap is not None and live_cap < capacity:
        # every live row sorted before the cut: what is past it is padding
        perm = perm[:live_cap]
        capacity = live_cap
    s_pid = pid_sortable[perm]
    s_active = s_pid < n_parts
    # position of each (sorted) row within its partition
    counts = jax.ops.segment_sum(s_active.astype(jnp.int32), s_pid,
                                 num_segments=n_parts + 1)[:n_parts]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    within = jnp.arange(capacity, dtype=jnp.int32) - offsets[
        jnp.clip(s_pid, 0, n_parts - 1)]
    ok = s_active & (within < bucket_cap)
    overflow = jnp.sum(s_active & ~ok)
    # Not-ok rows (inactive or overflow) scatter to row n_parts — out of
    # bounds, so mode="drop" discards them.  Clamping them into a valid slot
    # would zero live data whenever that slot is occupied (e.g. the last
    # bucket exactly full).
    dst_rows = jnp.where(ok, s_pid, n_parts)
    dst_cols = jnp.where(ok, within, 0)
    out_arrays = []
    for a in arrays:
        src = a[perm]
        buf = jnp.zeros((n_parts, bucket_cap), dtype=a.dtype)
        buf = buf.at[dst_rows, dst_cols].set(src, mode="drop")
        out_arrays.append(buf)
    sent_counts = jnp.minimum(counts, bucket_cap)
    return out_arrays, sent_counts, overflow


def exchange(axis_name: str, bucketed: Sequence[jax.Array],
             sent_counts: jax.Array):
    """all_to_all the bucket axis across the mesh (runs inside shard_map)."""
    recv = [jax.lax.all_to_all(b, axis_name, split_axis=0, concat_axis=0,
                               tiled=True)
            for b in bucketed]
    recv_counts = jax.lax.all_to_all(sent_counts.reshape(-1, 1), axis_name,
                                     split_axis=0, concat_axis=0,
                                     tiled=True).reshape(-1)
    return recv, recv_counts


def exchange_grouped_agg(axis_name: str, n_parts: int, bucket_cap: int,
                         keys: List[Value], contributions, active):
    """Full distributed group-by step, called inside shard_map:

    local sort-based partial agg → hash bucketize → ICI all_to_all →
    re-reduce received partials.  Returns (out_keys, out_vals, group_mask,
    overflow) with per-device results for that device's hash range.
    """
    # 1. local partial aggregation (shrinks the exchange payload)
    ok, ov, n_groups, gmask = groupby.group_reduce(keys, contributions, active)
    ops = [op for _, op in contributions]
    # 2. partition partial groups by key hash
    part_keys = ok
    pids = hash_ids(part_keys, n_parts)
    flat = []
    for d, v in ok:
        flat.append(d)
        flat.append(jnp.ones_like(d, dtype=jnp.bool_) if v is None else v)
    for d, v in ov:
        flat.append(d)
        flat.append(jnp.ones_like(d, dtype=jnp.bool_) if v is None else v)
    bucketed, sent, overflow = bucketize(pids, gmask, n_parts, bucket_cap, flat)
    # 3. collective
    recv, recv_counts = exchange(axis_name, bucketed, sent)
    # 4. unpack + final reduce over received rows
    total = n_parts * bucket_cap
    lane = jnp.arange(bucket_cap, dtype=jnp.int32)
    valid_rows = (lane[None, :] < recv_counts[:, None]).reshape(total)
    rk, rv = [], []
    i = 0
    for d, v in ok:
        rk.append((recv[i].reshape(total), recv[i + 1].reshape(total)))
        i += 2
    for d, v in ov:
        rv.append((recv[i].reshape(total), recv[i + 1].reshape(total)))
        i += 2
    fk, fv, fn, fmask = groupby.group_reduce(
        rk, [((d, v), op) for (d, v), op in zip(rv, ops)], valid_rows)
    # restore valid=None for originally non-null columns is unnecessary —
    # validity arrays are exact after the reduce.
    return fk, fv, fmask, overflow

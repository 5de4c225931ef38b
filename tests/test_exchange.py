"""ICI exchange kernel + multichip dryrun tests.

Runs on the 8-virtual-CPU-device mesh conftest.py sets up — the same
mechanism the driver uses to validate multi-chip sharding
(xla_force_host_platform_device_count).
"""

import jax
import jax.numpy as jnp

import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from spark_rapids_tpu.parallel.exchange import (
    bucketize, exchange_grouped_agg, hash_ids)
from spark_rapids_tpu.ops.hashing import hash_columns, spark_partition_id


# ---------------------------------------------------------------------------
# murmur3 golden values (computed by Apache Spark's Murmur3Hash, seed 42)
# ---------------------------------------------------------------------------

def test_murmur3_golden_int32():
    # spark.sql("SELECT hash(1)") -> -559580957; hash(0) -> 933211791
    h = hash_columns([(jnp.asarray([1, 0], dtype=jnp.int32), None)])
    assert int(h[0].astype(jnp.int32)) == -559580957
    assert int(h[1].astype(jnp.int32)) == 933211791


def test_murmur3_golden_int64():
    # spark.sql("SELECT hash(1L)") -> -1712319331; hash(0L) -> -1670924195
    h = hash_columns([(jnp.asarray([1, 0], dtype=jnp.int64), None)])
    assert int(h[0].astype(jnp.int32)) == -1712319331
    assert int(h[1].astype(jnp.int32)) == -1670924195


def test_murmur3_null_passthrough():
    # null column contributes nothing: hash == seed-only path of other col
    k1 = (jnp.asarray([5, 5], dtype=jnp.int32), None)
    k2 = (jnp.asarray([9, 9], dtype=jnp.int32),
          jnp.asarray([True, False]))
    h = hash_columns([k1, k2])
    h_only1 = hash_columns([k1])
    assert int(h[1]) == int(h_only1[1])
    assert int(h[0]) != int(h_only1[0])


def test_partition_id_non_negative():
    k = jnp.asarray(np.random.default_rng(0).integers(-10**9, 10**9, 256),
                    dtype=jnp.int64)
    pid = spark_partition_id([(k, None)], 7)
    assert int(jnp.min(pid)) >= 0 and int(jnp.max(pid)) < 7


# ---------------------------------------------------------------------------
# bucketize
# ---------------------------------------------------------------------------

def test_bucketize_exact_full_last_bucket_keeps_all_rows():
    # Regression: when the last partition's bucket is exactly full and there
    # are inactive rows, clamping those into the last slot zeroed live data.
    n_parts, bucket_cap = 2, 2
    vals = jnp.asarray([100, 101, 102, 103, 999, 999], dtype=jnp.int64)
    # rows 0-3 active, rows 4-5 inactive padding
    active = jnp.asarray([True, True, True, True, False, False])
    # force pids: two rows to partition 0, two to partition 1 (exactly full)
    pids = jnp.asarray([0, 0, 1, 1, 0, 0], dtype=jnp.int32)
    out, counts, overflow = bucketize(pids, active, n_parts, bucket_cap,
                                      [vals])
    assert int(overflow) == 0
    got = sorted(np.asarray(out[0]).reshape(-1).tolist())
    assert got == [100, 101, 102, 103]
    assert np.asarray(counts).tolist() == [2, 2]


def test_bucketize_overflow_detected_not_corrupting():
    n_parts, bucket_cap = 2, 2
    vals = jnp.asarray([1, 2, 3, 4, 5], dtype=jnp.int64)
    active = jnp.ones((5,), dtype=bool)
    pids = jnp.asarray([0, 0, 0, 1, 1], dtype=jnp.int32)  # p0 overflows by 1
    out, counts, overflow = bucketize(pids, active, n_parts, bucket_cap,
                                      [vals])
    assert int(overflow) == 1
    # partition 0 keeps its first bucket_cap rows in sort order
    assert np.asarray(counts).tolist() == [2, 2]
    p1 = sorted(np.asarray(out[0][1]).tolist())
    assert p1 == [4, 5]


@pytest.mark.parametrize("live,live_cap", [
    (0, 8), (5, 8), (8, 8), (37, 64), (200, 256), (256, 256), (200, None)])
def test_bucketize_live_cap_keeps_every_live_row(live, live_cap):
    """A caller that knows a bound on its live rows passes it: the rows
    past it once sorted are padding, and each bucket holds the rows it
    held, in whatever order."""
    rng = np.random.default_rng(live)
    cap, n_parts, bucket_cap = 256, 4, 256
    keys = jnp.asarray(rng.integers(0, 1 << 40, cap), dtype=jnp.int64)
    vals = jnp.asarray(rng.uniform(0, 10, cap))
    mask = np.zeros(cap, dtype=bool)
    mask[rng.choice(cap, live, replace=False)] = True
    pids = hash_ids([(keys, None)], n_parts)
    want = bucketize(pids, jnp.asarray(mask), n_parts, bucket_cap,
                     [keys, vals])
    got = bucketize(pids, jnp.asarray(mask), n_parts, bucket_cap,
                    [keys, vals], live_cap=live_cap)
    assert int(got[2]) == int(want[2]) == 0
    counts = np.asarray(got[1])
    assert counts.tolist() == np.asarray(want[1]).tolist()
    assert counts.sum() == live
    for p in range(n_parts):
        for g, w in zip(got[0], want[0]):
            assert g.shape == w.shape == (n_parts, bucket_cap)
            assert sorted(np.asarray(g)[p, :counts[p]].tolist()) \
                == sorted(np.asarray(w)[p, :counts[p]].tolist())


def test_bucketize_multiple_arrays_consistent():
    rng = np.random.default_rng(3)
    cap = 64
    keys = jnp.asarray(rng.integers(0, 50, cap), dtype=jnp.int64)
    vals = jnp.asarray(rng.uniform(0, 10, cap))
    active = jnp.asarray(rng.random(cap) < 0.8)
    pids = hash_ids([(keys, None)], 4)
    out, counts, overflow = bucketize(pids, active, 4, 32, [keys, vals])
    assert int(overflow) == 0
    # paired rows stay paired: rebuild (key, val) multiset of active rows
    got = set()
    k2d, v2d = np.asarray(out[0]), np.asarray(out[1])
    cnt = np.asarray(counts)
    for p in range(4):
        for i in range(cnt[p]):
            got.add((int(k2d[p, i]), round(float(v2d[p, i]), 6)))
    want = {(int(k), round(float(v), 6))
            for k, v, a in zip(np.asarray(keys), np.asarray(vals),
                               np.asarray(active)) if a}
    assert got == want


# ---------------------------------------------------------------------------
# exchange_grouped_agg over real shard_map meshes
# ---------------------------------------------------------------------------

def _run_exchange(n_devices, keys_np, vals_np, bucket_cap=256):
    devices = jax.devices()[:n_devices]
    assert len(devices) == n_devices
    mesh = Mesh(np.array(devices), ("data",))
    keys = jnp.asarray(keys_np)
    vals = jnp.asarray(vals_np)

    def step(k, v):
        active = jnp.ones(k.shape, dtype=bool)
        fk, fv, fmask, overflow = exchange_grouped_agg(
            "data", n_devices, bucket_cap, [(k, None)],
            [((v, None), "sum")], active)
        total = jnp.sum(jnp.where(fmask, fv[0][0], 0.0)).reshape(1)
        n_groups = jnp.sum(fmask.astype(jnp.int32)).reshape(1)
        return total, n_groups, overflow.reshape(1)

    fn = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(P("data"),) * 2,
                            out_specs=(P("data"),) * 3))
    totals, n_groups, overflow = fn(keys, vals)
    return (float(jnp.sum(totals)), int(jnp.sum(n_groups)),
            int(jnp.sum(overflow)))


@pytest.mark.parametrize("n_devices", [2, 4, 8])
def test_exchange_sum_matches_numpy(n_devices):
    rng = np.random.default_rng(n_devices)
    rows = n_devices * 512
    keys = rng.integers(0, 60, rows).astype(np.int64)
    vals = rng.uniform(0, 100, rows)
    total, n_groups, overflow = _run_exchange(n_devices, keys, vals)
    assert overflow == 0
    assert n_groups == len(np.unique(keys))
    np.testing.assert_allclose(total, vals.sum(), rtol=1e-9)


def test_exchange_skewed_keys():
    # 90% of rows carry one hot key — hammers a single destination device
    rng = np.random.default_rng(11)
    rows = 8 * 256
    keys = np.where(rng.random(rows) < 0.9, 7,
                    rng.integers(0, 64, rows)).astype(np.int64)
    vals = rng.uniform(0, 1, rows)
    total, n_groups, overflow = _run_exchange(8, keys, vals, bucket_cap=128)
    assert overflow == 0
    assert n_groups == len(np.unique(keys))
    np.testing.assert_allclose(total, vals.sum(), rtol=1e-9)


def test_exchange_overflow_detection():
    # bucket_cap too small for the number of distinct keys per destination
    rows = 4 * 512
    keys = np.arange(rows).astype(np.int64)  # all distinct: no local shrink
    vals = np.ones(rows)
    _, _, overflow = _run_exchange(4, keys, vals, bucket_cap=8)
    assert overflow > 0  # detected, not silently dropped


def test_exchange_multi_key():
    rng = np.random.default_rng(17)
    rows = 4 * 256
    k1 = rng.integers(0, 8, rows).astype(np.int64)
    k2 = rng.integers(0, 5, rows).astype(np.int32)
    vals = rng.uniform(0, 10, rows)
    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices), ("data",))

    def step(a, b, v):
        active = jnp.ones(a.shape, dtype=bool)
        fk, fv, fmask, overflow = exchange_grouped_agg(
            "data", 4, 256, [(a, None), (b, None)],
            [((v, None), "sum")], active)
        total = jnp.sum(jnp.where(fmask, fv[0][0], 0.0)).reshape(1)
        ng = jnp.sum(fmask.astype(jnp.int32)).reshape(1)
        return total, ng, overflow.reshape(1)

    fn = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(P("data"),) * 3,
                            out_specs=(P("data"),) * 3))
    totals, ng, overflow = fn(jnp.asarray(k1), jnp.asarray(k2),
                              jnp.asarray(vals))
    assert int(jnp.sum(overflow)) == 0
    import pandas as pd
    want_groups = pd.DataFrame({"a": k1, "b": k2}).drop_duplicates().shape[0]
    assert int(jnp.sum(ng)) == want_groups
    np.testing.assert_allclose(float(jnp.sum(totals)), vals.sum(), rtol=1e-9)


def test_dryrun_multichip_entrypoint():
    import __graft_entry__ as ge
    ge.dryrun_multichip(8)


def test_entry_x64_dtypes():
    import __graft_entry__ as ge
    fn, args = ge.entry()
    assert args[0].dtype == jnp.float64  # quantity
    assert args[1].dtype == jnp.float64  # price
    out = jax.jit(fn)(*args)
    assert np.isfinite(float(np.asarray(out[0])))

"""Dense aggregation scatters its live rows only (ops/dense_agg.py).

The update programs count the rows bound for the dense tables and, where
the rung of ``batch_utils.scatter_rung`` holds them, compact them on the
device before the scatters.  The compacted branch must leave the tables
exactly as the full path does: same values bit for bit (integers and
float64: the compaction is stable, so each slot accumulates in the same
order), same leftovers, same violation, same fetches.
"""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.ops import batch_utils, dense_agg
from spark_rapids_tpu.plan import physical
from spark_rapids_tpu.utils.metrics import QueryStats

CAP, D, RUNG = 4096, 1024, 256


def _bits(x):
    x = np.asarray(x)
    return x.view(np.int64) if x.dtype == np.float64 else x


def _inputs(rng, n_live, acc_dt, null_contribs):
    """A batch of CAP rows of which ``n_live`` are bound for the tables,
    scattered over the batch (not a prefix), with repeats per slot."""
    in_dom = np.zeros(CAP, bool)
    in_dom[rng.choice(CAP, n_live, replace=False)] = True
    idx = rng.integers(0, D // 4, CAP).astype(np.int64)
    sidx = np.where(in_dom, idx, D)
    if np.dtype(acc_dt).kind == "f":
        cd = rng.normal(0, 1e6, CAP) * 10.0 ** rng.integers(-8, 8, CAP)
    else:
        cd = rng.integers(-1 << 40, 1 << 40, CAP)
    cd = cd.astype(acc_dt)
    cv = (rng.random(CAP) < 0.8) if null_contribs else None
    r64 = (idx * 7).astype(np.int64)          # dependent on the slot
    r32 = (idx % 11).astype(np.int32)
    rv = rng.random(CAP) < 0.9
    return sidx, in_dom, (cd, cv), [(r64, None), (r32, rv)]


def _tables(acc_dt, op):
    def init(o, dt):
        return dense_agg.empty_table(o, D, dt)

    accs = (init(op, acc_dt),)
    res = tuple((init("min", dt), init("max", dt),
                 jnp.ones((D,), jnp.int8), jnp.zeros((D,), jnp.int8))
                for dt in (np.int64, np.int32))
    return accs, res, jnp.zeros((D,), jnp.int8)


def _update(rung, op, sidx, in_dom, contrib, res_vals, tables):
    """One update traced with the rule's answer pinned to ``rung``
    (``None``: the full path alone)."""
    accs, res, present = tables

    @jax.jit
    def f(sidx, in_dom, contrib, res_vals, accs, res, present):
        return dense_agg.update_tables(sidx, in_dom, [contrib], res_vals,
                                       accs, (op,), res, present)
    with mock.patch.object(batch_utils, "scatter_rung",
                           lambda cap, n64, n32: rung):
        return f(sidx, in_dom, contrib, res_vals, accs, res, present)


@pytest.mark.parametrize("n_live", [0, 1, RUNG, RUNG + 1, CAP])
@pytest.mark.parametrize("op,acc_dt,null_contribs", [
    ("sum", np.float64, False), ("sum", np.float64, True),
    ("sum", np.int64, True), ("min", np.float64, True),
    ("max", np.int64, False), ("min", np.int32, True)])
def test_compacted_branch_equals_full_path(n_live, op, acc_dt,
                                           null_contribs):
    rng = np.random.default_rng(n_live * 31 + len(op))
    sidx, in_dom, contrib, res_vals = _inputs(rng, n_live, acc_dt,
                                              null_contribs)
    want = _update(None, op, sidx, in_dom, contrib, res_vals,
                   _tables(acc_dt, op))
    assert int(want[3]) == 0
    for rung in (RUNG, CAP // 2):
        got = _update(rung, op, sidx, in_dom, contrib, res_vals,
                      _tables(acc_dt, op))
        assert int(got[3]) == int(n_live <= rung), (rung, n_live)
        for g, w in zip(jax.tree_util.tree_leaves(got[:3]),
                        jax.tree_util.tree_leaves(want[:3])):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(_bits(g), _bits(w))


def test_second_batch_accumulates_in_the_same_order():
    """Float64 sums over two batches, one compacted and one not, are the
    full path's to the last bit: rows reach each slot in their order."""
    rng = np.random.default_rng(5)
    tabs = {rung: _tables(np.float64, "sum") for rung in (None, RUNG)}
    for n_live in (CAP, RUNG - 3, 7):
        sidx, in_dom, contrib, res_vals = _inputs(rng, n_live, np.float64,
                                                  True)
        for rung in tabs:
            out = _update(rung, "sum", sidx, in_dom, contrib, res_vals,
                          tabs[rung])
            tabs[rung] = out[:3]
    for g, w in zip(jax.tree_util.tree_leaves(tabs[RUNG]),
                    jax.tree_util.tree_leaves(tabs[None])):
        np.testing.assert_array_equal(_bits(g), _bits(w))


# -- the rung rule ------------------------------------------------------------------

def test_rule_is_pure_and_offers_the_small_rung_at_q3s_shape():
    cap = 2_097_152
    assert batch_utils.scatter_rung(cap, 4, 5) \
        == batch_utils.scatter_rung(cap, 4, 5) == cap // 64


@pytest.mark.parametrize("n64,n32", [(4, 5), (3, 7), (1, 1), (0, 2),
                                     (1, 3), (2, 1)])
def test_rule_is_monotone_in_cap_and_silent_where_a_scatter_is_cheap(
        n64, n32):
    caps = [1 << k for k in range(7, 27)]
    offered = [batch_utils.scatter_rung(c, n64, n32) for c in caps]
    # under a couple of milliseconds of scatter: no branch at all
    for c, r in zip(caps, offered):
        if c * (n64 * batch_utils._SCATTER64_ROW_NS
                + n32 * batch_utils._SCATTER_ROW_NS) \
                < batch_utils._RUNG_FLOOR_NS:
            assert r is None, (c, r)
        else:
            assert r is None or (r & (r - 1) == 0 and r < c)
    assert offered[0] is None and offered[3] is None
    # once a capacity offers the rung, every larger one does, and the
    # rung never shrinks
    seen = False
    for prev, cur in zip(offered, offered[1:]):
        seen = seen or prev is not None
        if seen:
            assert cur is not None and cur >= prev, (prev, cur)
    assert seen


def test_rule_takes_non_power_of_two_capacities():
    for cap in (3 * (1 << 19), (1 << 21) + 12345):
        r = batch_utils.scatter_rung(cap, 4, 5)
        assert r & (r - 1) == 0 and cap >> 7 < r <= cap >> 6


# -- through the engine ---------------------------------------------------------------

def _force(monkeypatch, rung):
    """Pin the rule's answer and forget the programs traced under
    another."""
    monkeypatch.setattr(
        batch_utils, "scatter_rung",
        lambda cap, n64, n32: rung if rung and rung < cap else None)
    with physical._STAGE_CACHE_LOCK:
        physical._STAGE_CACHE.clear()


def _collect(sess, df):
    from spark_rapids_tpu.plan.physical import CollectExec, ExecContext
    phys = sess._plan_physical(df._plan)
    ctx = ExecContext(sess._tpu_conf(), device=sess.device)
    with QueryStats.scoped() as stats:
        tbl = CollectExec(phys).collect_arrow(ctx)
    metrics = {}
    for ms in ctx.metrics.values():
        for k, v in ms.values.items():
            if k.startswith("aggDense"):
                metrics[k] = metrics.get(k, 0) + v
    return tbl.to_pandas(), metrics, stats


def _same(got, want, keys):
    got = got.sort_values(keys).reset_index(drop=True)
    want = want.sort_values(keys).reset_index(drop=True)
    assert list(got.columns) == list(want.columns)
    assert len(got) == len(want)
    for c in got.columns:
        g, w = got[c].to_numpy(), want[c].to_numpy()
        # NULLs come back as NaN: compared by their bits like the rest
        np.testing.assert_array_equal(_bits(g), _bits(w), err_msg=c)


def _sparse_table(rng, n, with_nulls):
    """Rows of which a filter keeps about 1 in 200; keys in a bounded
    domain, NULL keys and (in later batches) keys outside the first
    batch's domain among the kept rows."""
    k = rng.integers(0, 3000, n).astype(np.int64)
    k[n // 2:] += rng.integers(0, 2, n - n // 2) * 50_000  # out of domain
    keep = rng.random(n) < 0.005
    v = rng.normal(0, 1e3, n)
    k_null = (rng.random(n) < 0.02) if with_nulls else np.zeros(n, bool)
    cols = {
        "k": pa.array(k, mask=k_null),
        # dependent on the key (one value for the NULL key too), so the
        # multi-key path holds
        "dep": np.where(k_null, 0, k * 3 + 1).astype(np.int64),
        "v": pa.array(v, mask=(rng.random(n) < 0.1) if with_nulls
                      else None),
        "w": rng.integers(-1000, 1000, n).astype(np.int64),
        "keep": keep.astype(np.int32),
    }
    return pa.table(cols)


@pytest.mark.parametrize("with_sel", [True, False])
@pytest.mark.parametrize("keys", [["k"], ["k", "dep"]])
def test_query_answers_are_the_full_paths_bit_for_bit(
        fresh_session, monkeypatch, keys, with_sel):
    from spark_rapids_tpu.sql import functions as F
    sess = fresh_session
    sess.conf.set("spark.rapids.tpu.sql.batchSizeRows", 1 << 16)
    rng = np.random.default_rng(len(keys) * 2 + with_sel)
    t = _sparse_table(rng, 200_000, with_nulls=True)

    def run():
        df = sess.create_dataframe(t)
        if with_sel:
            df = df.where(F.col("keep") == 1)
        return _collect(sess, df.group_by(*keys).agg(
            F.sum(F.col("v")).alias("s"), F.min(F.col("v")).alias("lo"),
            F.max(F.col("w")).alias("hi"), F.sum(F.col("w")).alias("sw")))

    _force(monkeypatch, None)
    want, m_full, st_full = run()
    assert m_full["aggDensePath"] >= 1
    assert m_full["aggDenseBatches"] >= 3
    assert m_full["aggDenseCompactedBatches"] == 0
    assert st_full.agg_dense_compacted_batches == 0
    # with the filter a 65,536-row batch keeps about 330 rows: under the
    # rung; without it nothing fits and the full path runs
    _force(monkeypatch, 1 << 10)
    got, m, st = run()
    _same(got, want, keys)
    assert m["aggDenseBatches"] == m_full["aggDenseBatches"] \
        == st.agg_dense_batches
    assert st.agg_dense_compacted_batches \
        == m["aggDenseCompactedBatches"] \
        == (m["aggDenseBatches"] if with_sel else 0)
    # the decision is made on the device; the count rides the tail fetch
    assert st.blocking_fetches == st_full.blocking_fetches
    assert st.async_fetches == st_full.async_fetches


def test_violation_in_a_compacted_batch_still_replays(fresh_session,
                                                      monkeypatch):
    """The residual violation is raised (and the input replayed through
    the sort path) when the violating rows arrive in a compacted batch."""
    from spark_rapids_tpu.sql import functions as F
    sess = fresh_session
    rng = np.random.default_rng(11)
    n, groups = 300_000, 500
    k = rng.integers(0, groups, n).astype(np.int64)
    r2 = (k * 3).astype(np.int64)
    # dependent within the 2^18-row sample prefix, violated after
    r2[(1 << 18) + 100:] = rng.integers(10_000, 10_050,
                                        n - (1 << 18) - 100)
    keep = (rng.random(n) < 0.01).astype(np.int32)
    t = pa.table({"k": k, "r2": r2, "v": rng.uniform(0, 10, n),
                  "keep": keep})
    _force(monkeypatch, 1 << 13)
    df = (sess.create_dataframe(t).where(F.col("keep") == 1)
          .group_by("k", "r2").agg(F.sum(F.col("v")).alias("s")))
    got, m, st = _collect(sess, df)
    assert m["aggDenseResidualFallback"] >= 1
    assert m["aggDenseCompactedBatches"] == m["aggDenseBatches"] >= 1
    pdf = t.to_pandas()
    want = (pdf[pdf.keep == 1].groupby(["k", "r2"])
            .agg(s=("v", "sum")).reset_index())
    got = got.sort_values(["k", "r2"]).reset_index(drop=True)
    want = want.sort_values(["k", "r2"]).reset_index(drop=True)
    assert len(got) == len(want)
    np.testing.assert_allclose(got["s"], want["s"], rtol=1e-9)


def test_the_rule_itself_engages_behind_a_selective_filter(fresh_session):
    """No patching: a 2^19-slot batch of which a filter keeps 1% takes
    the rule's rung, and the counters read what ran."""
    from spark_rapids_tpu.sql import functions as F
    sess = fresh_session
    with physical._STAGE_CACHE_LOCK:
        physical._STAGE_CACHE.clear()
    rng = np.random.default_rng(3)
    n = 300_000
    k = rng.integers(0, 5000, n).astype(np.int64)
    t = pa.table({"k": k, "dep": k * 2, "v": rng.uniform(0, 10, n),
                  "keep": (rng.random(n) < 0.01).astype(np.int32)})
    before = QueryStats.process().agg_dense_compacted_batches
    df = (sess.create_dataframe(t).where(F.col("keep") == 1)
          .group_by("k", "dep").agg(F.sum(F.col("v")).alias("s")))
    got, m, st = _collect(sess, df)
    assert m["aggDenseBatches"] == 1 == st.agg_dense_batches
    assert m["aggDenseCompactedBatches"] == 1 \
        == st.agg_dense_compacted_batches
    # folded outward as the other counters are
    assert QueryStats.process().agg_dense_compacted_batches == before + 1
    pdf = t.to_pandas()
    want = (pdf[pdf.keep == 1].groupby(["k", "dep"])
            .agg(s=("v", "sum")).reset_index())
    assert len(got) == len(want)
    np.testing.assert_allclose(
        got.sort_values("k")["s"].to_numpy(),
        want.sort_values("k")["s"].to_numpy(), rtol=1e-12)

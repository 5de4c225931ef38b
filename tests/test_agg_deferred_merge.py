"""The sort-path aggregate holds its compacted partial results and merges
them in one ``agg_merge_grouped`` when the held rows have doubled, the
stream ends, the rows could pass the batch budget, or the fan-in is
reached (``plan/physical._HeldPartials``): every case against pandas,
with the counters ``QueryStats.agg_merges`` / ``agg_merge_parts`` and a
look at what each merge took."""

import math

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from spark_rapids_tpu.batch import ColumnBatch, DeviceColumn, Field, Schema
from spark_rapids_tpu.ops import batch_utils
from spark_rapids_tpu.plan import physical
from spark_rapids_tpu.types import FLOAT64, INT64
from spark_rapids_tpu.utils.metrics import QueryStats

from .support import assert_rows_equal


def F():
    from spark_rapids_tpu.sql import functions
    return functions


class _Watch:
    """What the held partials did in one query: the rows and capacity of
    the parts of every merge, the rows held after every ``add``, and the
    rows of every batch handed on (``take``)."""

    def __init__(self, monkeypatch):
        self.merges, self.held, self.taken = [], [], []
        add, take = physical._HeldPartials.add, physical._HeldPartials.take
        merge = physical.AggregateExec._merge_partials
        watch = self

        def spy_add(self, part, bound=None):
            add(self, part, bound)
            watch.held.append((self.rows, len(self._parts), self._limit))

        def spy_take(self):
            out = take(self)
            if out is not None:
                watch.taken.append(out.num_rows)
            return out

        def spy_merge(self, parts, ops, n_keys, bound=None):
            watch.merges.append([(p.num_rows, p.capacity) for p in parts])
            return merge(self, parts, ops, n_keys, bound)

        monkeypatch.setattr(physical._HeldPartials, "add", spy_add)
        monkeypatch.setattr(physical._HeldPartials, "take", spy_take)
        monkeypatch.setattr(physical.AggregateExec, "_merge_partials",
                            spy_merge)


def _collect(sess, df):
    from spark_rapids_tpu.plan.physical import CollectExec, ExecContext
    phys = sess._plan_physical(df._plan)
    ctx = ExecContext(sess._tpu_conf(), device=sess.device)
    with QueryStats.scoped() as stats:
        tbl = CollectExec(phys).collect_arrow(ctx)
    metrics = {}
    for ms in ctx.metrics.values():
        for k, v in ms.values.items():
            metrics[k] = metrics.get(k, 0) + v
    return tbl, metrics, stats


def _rows(tbl):
    return list(zip(*[c.to_pylist() for c in tbl.columns]))


def _sparse(values):
    """int64 keys 2^40 apart: no dense domain holds them, so the
    aggregate takes the sort path."""
    return (np.asarray(values).astype(np.int64) << 40)


# -- a rollup over one batch: one merge a coarser set, none across sets -------

@pytest.mark.parametrize("n_keys", [3, 8])
def test_a_rollup_over_one_batch_merges_once(fresh_session, monkeypatch,
                                             n_keys):
    """The aggregate reduces the one batch at the finest set (every key)
    and holds that one partial, so no partials are merged; each coarser
    set is then ONE merge of the groups of the set one key finer
    (``rollup_levels_merged``), and no set is merged with another."""
    f = F()
    sess = fresh_session
    watch = _Watch(monkeypatch)
    rng = np.random.default_rng(n_keys)
    n = 2000
    cols = {"s": pa.array(rng.choice(["a", "b"], n).tolist())}
    for i in range(n_keys - 2):
        cols[f"k{i}"] = rng.integers(0, 2, n).astype(np.int64)
    cols["u"] = _sparse(np.arange(n))
    cols["v"] = np.round(rng.uniform(0, 100, n), 2)
    t = pa.table(cols)
    keys = list(cols)[:-1]
    before = QueryStats.process().rollup_levels_merged
    out = sess.create_dataframe(t).rollup(*keys).agg(
        f.sum(f.col("v")).alias("v"), f.grouping_id().alias("gid"))
    tbl, _m, st = _collect(sess, out)
    assert (st.agg_merges, st.agg_merge_parts) == (0, 0)
    assert watch.merges == [] and watch.taken == [n]
    assert st.rollup_levels_merged == n_keys and st.expand_slot_rows == 0
    # folded outward like every other counter
    assert QueryStats.process().rollup_levels_merged == before + n_keys
    pdf = t.to_pandas()
    parts = []
    for level in range(n_keys, -1, -1):
        ks = keys[:level]
        g = (pdf.groupby(ks, dropna=False)["v"].sum().reset_index()
             if ks else pd.DataFrame({"v": [pdf.v.sum()]}))
        for k in keys[level:]:
            g[k] = None
        g["gid"] = (1 << (n_keys - level)) - 1
        parts.append(g[keys + ["v", "gid"]].astype(object))
    want = pd.concat(parts, ignore_index=True)
    assert_rows_equal(_rows(tbl), [tuple(r) for r in want.itertuples(
        index=False)], approx_float=True)


def test_two_large_sets_that_fill_the_batch_still_merge_once(fresh_session,
                                                             monkeypatch):
    """The first two sets compact to half their batch's slots each and
    fill it together, but the second holds fewer rows than the first: a
    merge there would reduce the first set's rows twice for nothing.  A
    variance is not re-aggregated from its partials, so this rollup keeps
    Expand: one projection a set, each a partial held."""
    f = F()
    sess = fresh_session
    watch = _Watch(monkeypatch)
    rng = np.random.default_rng(29)
    n = 2000
    u = rng.integers(0, 900, n)
    w = u % 600
    t = pa.table({"a": w % 2, "b": w % 3, "w": _sparse(w), "u": _sparse(u),
                  "v": rng.integers(0, 100, n).astype(np.int64)})
    keys = ["a", "b", "w", "u"]
    out = sess.create_dataframe(t).rollup(*keys).agg(
        f.sum(f.col("v")).alias("v"), f.grouping_id().alias("gid"),
        f.variance(f.col("v")).alias("var_v"))
    tbl, _m, st = _collect(sess, out)
    assert st.rollup_levels_merged == 0 and st.expand_slot_rows > 0
    pdf = t.to_pandas()
    groups = [len(pdf.groupby(keys[:i])) if i else 1 for i in (4, 3, 2, 1, 0)]
    assert 512 < groups[1] < groups[0] < 1024
    assert watch.merges == [[(g, 1024) for g in groups]]
    assert (st.agg_merges, st.agg_merge_parts) == (1, 5)
    assert tbl.num_rows == sum(groups)
    got = {r[:4]: r[4] for r in _rows(tbl) if r[5] == 0}
    want = pdf.groupby(keys)["v"].sum()
    assert got == {k: int(x) for k, x in want.items()}


# -- many batches, every key its own group: about log2 merges -------------------

@pytest.mark.parametrize("n_batches", [8, 16])
def test_a_distinct_stream_merges_log2_of_its_batches(fresh_session,
                                                      monkeypatch,
                                                      n_batches):
    f = F()
    sess = fresh_session
    rows = 1024
    sess.conf.set("spark.rapids.tpu.sql.batchSizeRows", rows)
    watch = _Watch(monkeypatch)
    n = rows * n_batches
    rng = np.random.default_rng(n_batches)
    t = pa.table({"k": _sparse(rng.permutation(n)),
                  "v": rng.uniform(0, 10, n)})
    df = sess.create_dataframe(t).group_by("k").agg(
        f.sum(f.col("v")).alias("s"), f.count_star().alias("c"))
    tbl, _m, st = _collect(sess, df)
    # each merge doubles the result: 2, 3, 5, 9, ... parts
    assert st.agg_merges == int(math.log2(n_batches))
    assert [len(m) for m in watch.merges] == \
        [2 ** i + 1 for i in range(st.agg_merges)]
    assert st.agg_merge_parts == sum(len(m) for m in watch.merges)
    # one merge a part would have reduced 2 + 3 + ... + n_batches batches
    # of rows; held, every row is merged at most log2 times
    assert sum(r for m in watch.merges for r, _c in m) \
        <= n * st.agg_merges
    assert tbl.num_rows == n
    got = dict((k, (s, c)) for k, s, c in _rows(tbl))
    for k, v in zip(t["k"].to_pylist(), t["v"].to_pylist()):
        assert got[k] == (v, 1)


def test_held_rows_never_pass_the_limit(fresh_session, monkeypatch):
    """Whatever is held unmerged is at most ``limit`` rows: a part that
    would take the rows past it is merged in, and the merged count is
    what the re-partition fallback is asked about."""
    f = F()
    sess = fresh_session
    rows = 1024
    sess.conf.set("spark.rapids.tpu.sql.batchSizeRows", rows)
    # the buffer row is 32 bytes wide (the key, the sum's two, the count)
    sess.conf.set("spark.rapids.tpu.sql.batchSizeBytes", 5000 * 32)
    watch = _Watch(monkeypatch)
    n = rows * 12
    rng = np.random.default_rng(5)
    t = pa.table({"k": _sparse(rng.permutation(n)),
                  "v": rng.uniform(0, 10, n)})
    df = sess.create_dataframe(t).group_by("k").agg(
        f.sum(f.col("v")).alias("s"), f.count_star().alias("c"))
    tbl, m, _st = _collect(sess, df)
    assert {lim for _r, _p, lim in watch.held} == {5000}
    for held_rows, n_parts, limit in watch.held:
        assert held_rows <= limit or n_parts == 1
    # 5 batches of 1,024 distinct keys pass 5,000: the fallback fires
    # there, on the merged count, once
    assert m["aggRepartitions"] == 1 and watch.taken == [5 * rows]
    assert tbl.num_rows == n


# -- many batches, few groups: the fan-in bounds a concat ----------------------

def test_a_low_cardinality_stream_respects_the_fan_in(fresh_session,
                                                      monkeypatch):
    f = F()
    sess = fresh_session
    rows = 32768     # 16 partials of 1,024 slots do not fill one batch
    sess.conf.set("spark.rapids.tpu.sql.batchSizeRows", rows)
    watch = _Watch(monkeypatch)
    n_batches = 20
    n = rows * n_batches
    rng = np.random.default_rng(7)
    t = pa.table({"k": _sparse(rng.integers(0, 10, n)),
                  "v": rng.integers(0, 100, n).astype(np.int64)})
    df = sess.create_dataframe(t).group_by("k").agg(
        f.sum(f.col("v")).alias("s"), f.max(f.col("v")).alias("hi"))
    tbl, _m, st = _collect(sess, df)
    fan_in = physical._MERGE_FAN_IN
    assert [len(m) for m in watch.merges] == \
        [fan_in, n_batches - fan_in + 1]
    assert (st.agg_merges, st.agg_merge_parts) == (2, n_batches + 1)
    want = t.to_pandas().groupby("k").agg(s=("v", "sum"), hi=("v", "max"))
    assert_rows_equal(_rows(tbl), [(int(k), int(r.s), int(r.hi))
                                   for k, r in want.iterrows()])


# -- first / last see rows in arrival order ------------------------------------

@pytest.mark.parametrize("ignore_nulls", [False, True])
def test_first_and_last_keep_arrival_order(fresh_session, monkeypatch,
                                           ignore_nulls):
    """Partials are concatenated the last merge's result first, then in
    arrival order, and the group sort is stable: ``first`` / ``last``
    (and their ``ignore_nulls`` forms, first_valid / last_valid) answer
    as one pass over the rows in order does."""
    f = F()
    sess = fresh_session
    rows = 4096
    sess.conf.set("spark.rapids.tpu.sql.batchSizeRows", rows)
    watch = _Watch(monkeypatch)
    n = rows * 8
    rng = np.random.default_rng(11 + ignore_nulls)
    v = rng.integers(0, 1_000_000, n).astype(object)
    v[rng.random(n) < 0.4] = None
    t = pa.table({"k": _sparse(rng.integers(0, 100, n)),
                  "v": pa.array(v.tolist(), type=pa.int64())})
    df = sess.create_dataframe(t).group_by("k").agg(
        f.first(f.col("v"), ignore_nulls).alias("fi"),
        f.last(f.col("v"), ignore_nulls).alias("la"))
    tbl, _m, st = _collect(sess, df)
    # four 1,024-slot partials fill a 4,096-slot batch: merges of
    # several partials, the later ones with a result in front
    assert [len(m) for m in watch.merges] == [4, 4, 2]
    assert (st.agg_merges, st.agg_merge_parts) == (3, 10)
    want = {}
    for k, x in zip(t["k"].to_pylist(), t["v"].to_pylist()):
        if ignore_nulls and x is None:
            want.setdefault(k, [None, None])
            continue
        fi, _la = want.get(k, [None, None])
        if k not in want or (ignore_nulls and fi is None):
            want[k] = [x, x]
        else:
            want[k][1] = x
    assert_rows_equal(_rows(tbl), [(k, a, b) for k, (a, b) in want.items()])


# -- the fallbacks fire on the counts one merge a part gave ---------------------

def _one_merge_a_part(keys, rows, limit):
    """What a loop that merges every part as it arrives hands on: the
    distinct keys seen whenever they pass ``limit`` (then it starts
    again), and what is left at the end."""
    handed, seen = [], set()
    for i in range(0, len(keys), rows):
        seen.update(keys[i:i + rows])
        if len(seen) > limit:
            handed.append(len(seen))
            seen = set()
    return handed, len(seen)


@pytest.mark.parametrize("mode", ["complete", "partial"])
def test_fallbacks_fire_at_the_same_merged_counts(fresh_session,
                                                  monkeypatch, mode):
    f = F()
    sess = fresh_session
    rows = 2048
    sess.conf.set("spark.rapids.tpu.sql.batchSizeRows", rows)
    sess.conf.set("spark.rapids.tpu.sql.batchSizeBytes", 3000 * 16)
    if mode == "partial":
        sess.conf.set(
            "spark.rapids.tpu.sql.agg.singleProcessComplete", False)
        sess.conf.set("spark.rapids.tpu.sql.agg.skipPartialAggRatio", 1.0)
    watch = _Watch(monkeypatch)
    rng = np.random.default_rng(13)
    n = rows * 11
    keys = rng.integers(0, 5000, n)
    t = pa.table({"k": _sparse(keys), "v": rng.uniform(0, 10, n)})
    df = sess.create_dataframe(t).group_by("k").agg(
        f.min(f.col("v")).alias("lo"))
    tbl, m, _st = _collect(sess, df)
    limit = watch.held[0][2]    # batchSizeBytes over the buffer's width
    assert 1000 < limit < 3000
    handed, left = _one_merge_a_part(keys.tolist(), rows, limit)
    assert len(handed) >= 2
    if mode == "complete":
        # the first pass over the limit splits into buckets, which the
        # per-bucket merges own from there on
        assert m["aggRepartitions"] == 1
        assert watch.taken == handed[:1]
    else:
        # every early emit, then the rest at the stream's end (the final
        # aggregate finalizes a partition a batch and holds nothing)
        assert left > 0 and watch.taken == handed + [left]
    want = t.to_pandas().groupby("k")["v"].min()
    got = dict(_rows(tbl))
    assert len(got) == len(want)
    for k, lo in want.items():
        assert got[int(k)] == lo


# -- the replay after a dense attempt uses the same rule -----------------------

def test_the_replay_holds_its_partials_too(fresh_session, monkeypatch):
    f = F()
    sess = fresh_session
    rows = 65536
    sess.conf.set("spark.rapids.tpu.sql.batchSizeRows", rows)
    watch = _Watch(monkeypatch)
    rng = np.random.default_rng(17)
    n, groups = 300_000, 500
    k = rng.integers(0, groups, n).astype(np.int64)
    r2 = (k * 3).astype(np.int64)
    # dependent within the sample prefix, violated after: the dense
    # multi-key path gives up at the stream's end and replays
    r2[(1 << 18) + 100:] = rng.integers(10_000, 10_050,
                                        n - (1 << 18) - 100)
    t = pa.table({"k": k, "r2": r2, "v": rng.uniform(0, 10, n)})
    df = sess.create_dataframe(t).group_by("k", "r2").agg(
        f.sum(f.col("v")).alias("s"))
    tbl, m, st = _collect(sess, df)
    assert m["aggDenseResidualFallback"] >= 1
    n_batches = -(-n // rows)
    assert [len(x) for x in watch.merges] == [n_batches]
    assert (st.agg_merges, st.agg_merge_parts) == (1, n_batches)
    want = (t.to_pandas().groupby(["k", "r2"]).agg(s=("v", "sum"))
            .reset_index())
    got = tbl.to_pandas().sort_values(["k", "r2"]).reset_index(drop=True)
    want = want.sort_values(["k", "r2"]).reset_index(drop=True)
    assert len(got) == len(want)
    np.testing.assert_allclose(got["s"], want["s"], rtol=1e-12)


# -- the bounded (grid) form: static slices, no fetch a batch -------------------

def test_the_grid_form_stays_free_of_blocking_fetches(fresh_session,
                                                      monkeypatch):
    f = F()
    sess = fresh_session
    rows = 8192
    sess.conf.set("spark.rapids.tpu.sql.batchSizeRows", rows)
    rng = np.random.default_rng(19)

    def run(n_batches):
        n = rows * n_batches
        t = pa.table({
            "a": pa.array(rng.choice(["x", "y", "z"], n).tolist()),
            "b": pa.array(rng.choice(["p", "q"], n).tolist()),
            "v": rng.integers(0, 100, n).astype(np.int64)})
        df = sess.create_dataframe(t).group_by("a", "b").agg(
            f.sum(f.col("v")).alias("s"))
        tbl, _m, st = _collect(sess, df)
        want = t.to_pandas().groupby(["a", "b"])["v"].sum()
        assert_rows_equal(_rows(tbl), [(a, b, int(s))
                                       for (a, b), s in want.items()])
        return st

    few = run(2)
    watch = _Watch(monkeypatch)
    many = run(6)
    # six bounded partials of 1,024 slots, one merge at the stream's end
    assert [len(x) for x in watch.merges] == [6]
    assert all(cap == 1024 for _r, cap in watch.merges[0])
    assert (few.agg_merges, many.agg_merges) == (1, 1)
    # nothing is fetched for a partial or for a merge: four more batches
    # cost no blocking fetch
    assert many.blocking_fetches == few.blocking_fetches


# -- the concatenation of compact partials ---------------------------------------

def _batch(rng, n_live, cap, with_valid):
    k = np.zeros(cap, dtype=np.int64)
    k[:n_live] = rng.integers(0, 1 << 40, n_live)
    v = np.full(cap, np.nan)
    v[:n_live] = rng.uniform(0, 1, n_live)
    valid = None
    if with_valid:
        valid = np.zeros(cap, dtype=bool)
        valid[:n_live] = rng.random(n_live) < 0.8
    import jax.numpy as jnp
    schema = Schema([Field("k", INT64, False), Field("v", FLOAT64, True)])
    return ColumnBatch(schema, [
        DeviceColumn(INT64, jnp.asarray(k), None),
        DeviceColumn(FLOAT64, jnp.asarray(v),
                     None if valid is None else jnp.asarray(valid))],
        n_live)


def _live(batch):
    n = batch.num_rows
    out = []
    for c in batch.columns:
        d = np.asarray(c.data)[:n]
        v = np.ones(n, dtype=bool) if c.valid is None \
            else np.asarray(c.valid)[:n]
        out.append([x if ok else None for x, ok in zip(d.tolist(), v)])
    return list(zip(*out))


@pytest.mark.parametrize("lives,caps,valids,out_cap", [
    # the rung over the live rows is under the rung over the slots
    ((1500, 100, 7, 1), (2048, 1024, 1024, 1024), (True, True, True, True),
     2048),
    # validity on some parts only; the last part ends past the rung
    ((900, 100, 1020), (1024, 1024, 1024), (False, True, False), 2048),
    ((1024, 0, 1024), (1024, 1024, 1024), (True, False, True), 2048),
    # full parts: the same rung either way, the plain concat runs
    ((2048, 2048), (2048, 2048), (True, True), 4096),
])
def test_concat_packed_lays_live_rows_end_to_end(lives, caps, valids,
                                                 out_cap):
    rng = np.random.default_rng(sum(lives))
    parts = [_batch(rng, n, c, w) for n, c, w in zip(lives, caps, valids)]
    out = batch_utils.concat_packed(parts)
    assert out.capacity == out_cap
    if out.sel is not None:     # the plain concat's mask
        out = batch_utils.compact(out)
    assert out.num_rows == sum(lives)
    assert _live(out) == [r for p in parts for r in _live(p)]


def test_concat_packed_leaves_masked_batches_to_the_plain_concat():
    import jax.numpy as jnp
    rng = np.random.default_rng(23)
    a, b = _batch(rng, 600, 1024, True), _batch(rng, 50, 1024, True)
    b = ColumnBatch(b.schema, b.columns, 1024, jnp.arange(1024) < 50)
    out = batch_utils.concat_packed([a, b])
    assert out.capacity == 2048 and out.sel is not None
    assert _live(batch_utils.compact(out)) == _live(a) + _live(b)[:50]

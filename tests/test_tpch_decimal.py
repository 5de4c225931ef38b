"""The ``tpch_sf1_decimal`` deployment on the CPU: TPC-H Q1 and Q6 over
``lineitem`` with its money DECIMAL(15,2), through ``read_parquet`` ->
DataFrame -> ``collect()``, equal to the plain references of
``benchmark/queries/tpch_decimal`` exactly (values, scales, types), NULLs
in every money column and batch boundaries inside every group; the
decimal decode of both parquet encodings, the ``scan:decimal`` span in the
host-time account, the ``wide_decimal_slots`` counter; and the float64
twin's programs, which lower as they did before decimals had Spark's types.
"""

import hashlib
import os
import sys
import zlib
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.utils import tracing
from spark_rapids_tpu.utils.metrics import QueryStats

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
MONEY = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
SMALL = {"spark.rapids.tpu.sql.batchSizeRows": 8192,
         "spark.rapids.tpu.sql.reader.batchSizeBytes": 8192 * 64}
# Spark 3.x's type of every output column (the query files' docstrings)
TYPES = {"q1": ["string", "string", "decimal(25,2)", "decimal(25,2)",
                "decimal(38,4)", "decimal(38,6)", "decimal(19,6)",
                "decimal(19,6)", "decimal(19,6)", "bigint"],
         "q6": ["decimal(38,4)"]}


@pytest.fixture(scope="module")
def bench():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from harness import compare, sources
    return (sources.load_module([BENCH], "datagen", "tpch_decimal.py"),
            compare,
            {q: sources.load_module([BENCH], "queries", "tpch_decimal",
                                    q + ".py") for q in ("q1", "q6")})


def _with_nulls(table):
    """A NULL in every money column, each on rows of its own."""
    for k, name in enumerate(MONEY):
        i = table.column_names.index(name)
        hole = pa.array(np.arange(table.num_rows) % (37 + 2 * k) == k)
        col = pc.if_else(hole, pa.scalar(None, table.schema.field(i).type),
                         table.column(i))
        table = table.set_column(i, name, col)
    return table


@pytest.fixture(scope="module", params=[2**31 + 39, 1994])
def world(request, bench, session, tmp_path_factory):
    gen = bench[0]
    d = str(tmp_path_factory.mktemp("tpch_decimal"))
    path = gen.gen(0.01, request.param, d)["lineitem"]
    table = _with_nulls(pq.read_table(path))
    pq.write_table(table, path, row_group_size=20_000,
                   store_decimal_as_integer=True)
    return request.param, path, table.to_pandas()


@pytest.fixture()
def small_batches(session):
    for k, v in SMALL.items():
        session.conf.set(k, v)
    yield session
    for k in SMALL:
        session.conf.unset(k)


@pytest.mark.parametrize("name", ["q1", "q6"])
def test_query_equals_its_reference(name, world, bench, small_batches,
                                    monkeypatch):
    """A pool of four parameter sets, as the cell draws them: the rows
    equal the reference's exactly (each Decimal at Spark's scale), the
    types are Spark's, the plan runs on the device, and Q1's input spans
    several batches."""
    from spark_rapids_tpu.sql.dataframe import DataFrame
    _, compare, queries = bench
    seed, path, pdf = world
    q = queries[name]
    dfs = {"lineitem": small_batches.read_parquet(path)}
    seen = []
    collect = DataFrame.collect

    def noting(df):
        seen.append((df.explain_string(), [str(f.dtype) for f in df.schema]))
        return collect(df)
    monkeypatch.setattr(DataFrame, "collect", noting)
    rng = np.random.default_rng([seed, zlib.crc32(name.encode()), 7])
    for p in [q.params(rng) for _ in range(4)]:
        with QueryStats.scoped() as qs:
            got = q.run(dfs, p)
        want = q.reference({"lineitem": pdf}, p)
        assert [tuple(map(repr, r)) for r in sorted(got, key=repr)] == \
            [tuple(map(repr, r)) for r in sorted(want, key=repr)], p
        assert compare.rows_rel_err(got, want) == 0.0
        plan, types = seen[-1]
        assert types == TYPES[name]
        assert "!" not in "".join(plan.splitlines()[2:]), plan
        assert "CpuOp" not in plan and qs.cpu_fallback_nodes == 0
        if name == "q1":
            # two wide outputs a slot, over two batches or more
            assert qs.wide_decimal_slots >= 2 * 2 * SMALL[
                "spark.rapids.tpu.sql.batchSizeRows"]
            assert len(got) == 4 and all(None not in r for r in got)
        assert qs.decimal_decode_s > 0


def test_the_precision_control_fails(world, bench):
    """The same data's answers in float64, and a Decimal rounded to the
    wrong scale, are both wrong under the configuration's limits."""
    import json
    _, compare, queries = bench
    with open(os.path.join(BENCH, "configs", "tpch_sf1_decimal.json")) as f:
        limits = json.load(f)["limits"]
    want = queries["q1"].reference({"lineitem": world[2]},
                                   {"delta_days": 90})
    floats = [tuple(float(x) if isinstance(x, Decimal) else x for x in r)
              for r in want]
    coarse = [tuple(x.quantize(Decimal("0.01")) if isinstance(x, Decimal)
                    else x for x in r) for r in want]
    for bad in (floats, coarse):
        ok, _, _, _ = compare.judge([("q1", 0, bad)], {("q1", 0): want},
                                    limits)
        assert not ok


@pytest.mark.parametrize("store_as_int", [True, False],
                         ids=["int64", "flba"])
@pytest.mark.parametrize("p,s", [(15, 2), (18, 4), (38, 10)])
def test_the_decode_equals_decimal_for_every_value(session, tmp_path,
                                                   store_as_int, p, s):
    from spark_rapids_tpu.batch import decimal_scaled
    rng = np.random.default_rng([p, s])
    digits = rng.integers(1, p + 1, 3000)
    vals = [Decimal(f"{'-' if rng.integers(0, 2) else ''}"
                    f"{''.join(map(str, rng.integers(0, 10, d)))}E-{s}")
            for d in digits]
    top = 10 ** p - 1
    vals[:4] = [Decimal(f"{top}E-{s}"), Decimal(f"-{top}E-{s}"),
                Decimal(f"0E-{s}"), None]
    vals[1000::97] = [None] * len(vals[1000::97])
    t = pa.table({"d": pa.array(vals, type=pa.decimal128(p, s))})
    path = str(tmp_path / "d.parquet")
    pq.write_table(t, path, store_decimal_as_integer=store_as_int,
                   row_group_size=1000)
    kind = pq.ParquetFile(path).metadata.row_group(0).column(0).physical_type
    assert kind == ("INT64" if store_as_int and p <= 18
                    else "FIXED_LEN_BYTE_ARRAY")
    assert [r[0] for r in session.read_parquet(path).collect()] == vals
    # the buffer view honours an array's offset
    arr = t.column(0).chunk(0).slice(3, 500)
    data, valid = decimal_scaled(arr, p > 18)
    lo = data if data.ndim == 1 else data[:, 0]
    hi = (lo >> 63) if data.ndim == 1 else data[:, 1]
    got = [None if not ok else (int(h) << 64) + (int(x) & (2 ** 64 - 1))
           for x, h, ok in zip(lo, hi, valid)]
    assert got == [None if v is None else T.unscaled_value(v, s)
                   for v in vals[3:503]]


@pytest.mark.parametrize("depth", [0, 2])
def test_the_decimal_decode_is_charged_to_convert(session, world, depth):
    """``scan:decimal`` is ``host_exec`` on the driving thread and the
    convert term on a producer's; the nine terms still sum to the wall and
    the seven parts to the wait."""
    assert tracing._term_of("scan:decimal") == "host_exec"
    assert tracing._slot_of("scan:decimal") == tracing._CONVERT
    session.conf.set("spark.rapids.tpu.sql.pipeline.depth", depth)
    try:
        df = session.read_parquet(world[1]).agg(
            F.sum(F.col("l_extendedprice") * F.col("l_discount")).alias("r"))
        df.collect()
        with QueryStats.scoped() as st:
            df.collect()
    finally:
        session.conf.unset("spark.rapids.tpu.sql.pipeline.depth")
    terms = [getattr(st, f"acct_{t}_s") for t in tracing.ACCOUNT_TERMS]
    assert sum(terms) == pytest.approx(st.query_wall_s, rel=0.01)
    parts = [getattr(st, f"acct_h2d_{t}_s") for t in tracing.RESOLVED_TERMS]
    assert sum(parts) == pytest.approx(st.acct_h2d_wait_s, rel=1e-9,
                                       abs=1e-12)
    assert st.decimal_decode_s > 0
    names = {e[1] for e in session.last_trace().events}
    assert "scan:decimal" in names


def test_wide_decimal_slots_equals_its_reckoning(session):
    """One batch of 3,000 rows: Q6's product is one wide output a slot, Q1's
    two (the base price and the charge); the narrow ``1 - discount`` and
    a sum's lanes count nothing."""
    from spark_rapids_tpu.batch import bucket_capacity
    n = 3000
    cents = np.arange(n, dtype=np.int64) * 7 + 100
    dec = pa.array([Decimal(f"{c}E-2") for c in cents],
                   type=pa.decimal128(15, 2))
    df = session.create_dataframe(pa.table({
        "k": pa.array(np.arange(n) % 4), "price": dec, "disc": dec}))
    cap = bucket_capacity(n)
    base = F.col("price") * (1 - F.col("disc"))
    for q, wide in (
            (df.agg(F.sum(F.col("price") * F.col("disc")).alias("r")), 1),
            (df.group_by("k").agg(F.sum(base).alias("a"),
                                  F.sum(base * (1 + F.col("disc"))).alias("b"),
                                  F.avg(F.col("price")).alias("c")), 2),
            (df.agg(F.sum(F.col("price")).alias("s")), 0)):
        with QueryStats.scoped() as st:
            q.collect()
        assert st.wide_decimal_slots == wide * cap


# The sha256 over the sorted lowered StableHLO texts of every program the
# float64 twin's Q1 and Q6 (benchmark/queries/tpch) run at SF 0.002, seed
# 2^31 + 31, as the parent of the decimal-types change lowers them.
TWIN_DIGEST = \
    "029c98ce67494642e52c2cc99eec67dc321d3ba6870f67980329abff61be2598"


def test_the_float_twins_programs_lower_as_before(fresh_session,
                                                  tmp_path, monkeypatch):
    import jax

    import spark_rapids_tpu.plan.physical as P
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from harness import sources
    seen = {}
    call = P._Program.__call__

    def lowering(self, *a, **k):
        leaves = jax.tree_util.tree_leaves((a, k))
        key = (self.name, str(jax.tree_util.tree_structure((a, k))),
               str([(getattr(x, "shape", None),
                     str(getattr(x, "dtype", type(x)))) for x in leaves]))
        if key not in seen:
            seen[key] = hashlib.sha256(
                self.call.lower(*a, **k).as_text().encode()).hexdigest()
        return call(self, *a, **k)
    monkeypatch.setattr(P._Program, "__call__", lowering)
    gen = sources.load_module([BENCH], "datagen", "tpch.py")
    paths = gen.gen(0.002, 2**31 + 31, str(tmp_path), ["lineitem"])
    dfs = {"lineitem": fresh_session.read_parquet(paths["lineitem"])}
    for name in ("q1", "q6"):
        q = sources.load_module([BENCH], "queries", "tpch", name + ".py")
        q.run(dfs, q.params(np.random.default_rng(1)))
    got = hashlib.sha256("".join(sorted(
        f"{k[0]}{v}" for k, v in seen.items())).encode()).hexdigest()
    assert {k[0] for k in seen} >= {"stage", "agg_grid",
                                    "agg_ungrouped_fused"}
    assert got == TWIN_DIGEST


_ARROW_COMPARE = {"<": pc.less, "<=": pc.less_equal, ">": pc.greater,
                  ">=": pc.greater_equal, "==": pc.equal}


@pytest.mark.parametrize("op", sorted(_ARROW_COMPARE))
def test_the_scaled_compare_keeps_arrows_rows(op):
    """A decimal conjunct on the host compares the scaled words with the
    literal at the column's scale: it keeps Arrow's rows across chunks, an
    offset and NULLs, for whole and fractional literals; a literal finer
    than the column's scale, a float or one past 64 bits is left to Arrow."""
    from spark_rapids_tpu.io.parquet import _scaled_compare
    rng = np.random.default_rng(5)
    vals = [None if i % 7 == 0 else Decimal(int(v)).scaleb(-2)
            for i, v in enumerate(rng.integers(-10**6, 10**6, 3000))]
    arr = pa.array(vals, type=pa.decimal128(15, 2))
    col = pa.chunked_array([arr.slice(3, 1000), arr.slice(1003)])
    for lit in (Decimal("12.34"), 5, Decimal("-0.01"), Decimal("0"),
                Decimal("1E+3"), Decimal(vals[10])):
        want = pc.fill_null(_ARROW_COMPARE[op](col, lit), False)
        assert (_scaled_compare(col, op, lit, None)
                == want.to_numpy(zero_copy_only=False)).all(), lit
    for lit in (Decimal("12.345"), 0.5, 10 ** 20, Decimal("NaN")):
        assert _scaled_compare(col, op, lit, None) is None


@pytest.mark.parametrize("n,cut,staged", [
    (3_000_000, 2_850_000, 3_000_000),   # 95% kept, same bucket: whole
    (3_000_000, 150_000, 150_000),       # 5% kept: cut on the host
    (20_000, 19_000, 19_000)])           # 160 KB: cut, cheaper than a sync
def test_a_filter_that_keeps_the_bucket_is_left_to_the_device(
        session, tmp_path, n, cut, staged):
    """The scan filters a batch on the host only where that shrinks its
    capacity bucket or the batch is small: a conjunct that keeps 95% of a
    24 MB batch leaves it whole (the device filter drops the rest); the
    answer is the same."""
    from spark_rapids_tpu.io.parquet import ParquetSource
    path = str(tmp_path / "x.parquet")
    pq.write_table(pa.table({"x": np.arange(n, dtype=np.int64)}), path,
                   row_group_size=n)
    src = ParquetSource(path, predicates=[("x", "<", cut)], batch_rows=n)
    assert sum(t.num_rows for t in src._read_all()) == staged
    got = session.read_parquet(path).where(F.col("x") < cut) \
        .agg(F.count_star().alias("n")).collect()
    assert got == [(cut,)]


def test_the_exact_finalize_reads_only_groups_that_hold_rows(session,
                                                             monkeypatch):
    """An exact average is worked out for the groups that hold rows, not
    for every slot of the aggregate's output (string keys, as Q1's, take
    the grid, whose output is 1,024 slots for four groups)."""
    from spark_rapids_tpu import aggfns
    counts = []
    real = aggfns.Average.decimal_value

    def noting(self, total, n):
        counts.append(n)
        return real(self, total, n)
    monkeypatch.setattr(aggfns.Average, "decimal_value", noting)
    n = 3000
    dec = pa.array([Decimal(f"{7 * i + 100}E-2") for i in range(n)],
                   type=pa.decimal128(15, 2))
    df = session.create_dataframe(pa.table({
        "k": pa.array(np.array(list("ANRO"))[np.arange(n) % 4]),
        "price": dec}))
    got = df.group_by("k").agg(F.avg(F.col("price")).alias("a")).collect()
    assert len(got) == 4 and counts == [750] * 4

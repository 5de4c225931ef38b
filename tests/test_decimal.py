"""Decimal semantics: operand promotion, mixed-type compare/divide, agg.

Regression tests for the round-1 advisor finding: decimal operands were
astype'd without rescaling, so decimal(5,2) 2.00 == 2 matched nothing and
1.50/2 returned 75.0.  Reference semantics: GpuCast.scala / decimal rules in
arithmetic.scala (Spark widerDecimalType promotion).
"""

from decimal import Decimal

import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.sql import functions as F


def _df(session, rows, scale=2, precision=5):
    t = pa.table({
        "d": pa.array([None if r is None else Decimal(r).quantize(
            Decimal(1).scaleb(-scale)) for r in rows],
            type=pa.decimal128(precision, scale)),
        "i": pa.array(list(range(len(rows))), type=pa.int32()),
    })
    return session.create_dataframe(t)


class TestDecimalPromotion:
    def test_decimal_eq_int_literal(self, session):
        df = _df(session, ["1.00", "2.00", "2.50", None])
        out = df.where(F.col("d") == 2).to_pandas()
        assert list(out["i"]) == [1]

    def test_decimal_lt_int_literal(self, session):
        df = _df(session, ["1.00", "2.00", "2.50", "3.00"])
        out = df.where(F.col("d") < 3).to_pandas()
        assert sorted(out["i"]) == [0, 1, 2]

    def test_decimal_divide_int(self, session):
        df = _df(session, ["1.50", "3.00"])
        out = df.select((F.col("d") / 2).alias("h")).to_pandas()
        assert list(out["h"]) == [0.75, 1.5]

    def test_decimal_divide_decimal(self, session):
        df = _df(session, ["1.50", "3.00"])
        out = df.select((F.col("d") / F.col("d")).alias("r")).to_pandas()
        assert list(out["r"]) == [1.0, 1.0]

    def test_mixed_scale_add(self, session):
        t = pa.table({
            "a": pa.array([Decimal("1.5")], type=pa.decimal128(5, 1)),
            "b": pa.array([Decimal("0.25")], type=pa.decimal128(5, 2)),
        })
        df = session.create_dataframe(t)
        q = df.select((F.col("a") + F.col("b")).alias("s"))
        # Spark: max(s1, s2) + max(p1 - s1, p2 - s2) + 1 digits
        assert str(q.schema.fields[0].dtype) == "decimal(7,2)"
        out = q.to_pandas()
        assert out["s"][0] == Decimal("1.75")

    def test_mixed_scale_compare(self, session):
        t = pa.table({
            "a": pa.array([Decimal("1.5"), Decimal("2.0")],
                          type=pa.decimal128(5, 1)),
            "b": pa.array([Decimal("1.50"), Decimal("2.01")],
                          type=pa.decimal128(6, 2)),
        })
        df = session.create_dataframe(t)
        out = df.where(F.col("a") == F.col("b")).to_pandas()
        assert len(out) == 1
        assert out["a"][0] == Decimal("1.5")

    def test_decimal_plus_int_column(self, session):
        df = _df(session, ["1.00", "2.00", "3.00"])
        q = df.select((F.col("d") + F.col("i")).alias("s"))
        # an int column is decimal(10,0) in decimal arithmetic
        assert str(q.schema.fields[0].dtype) == "decimal(13,2)"
        out = q.to_pandas()
        assert list(out["s"]) == [Decimal("1.00"), Decimal("3.00"),
                                  Decimal("5.00")]

    def test_decimal_mul_int(self, session):
        df = _df(session, ["1.25", "2.00"])
        q = df.select((F.col("d") * 4).alias("m"))
        # the literal 4 is decimal(1,0): p1 + p2 + 1 digits, s1 + s2
        assert str(q.schema.fields[0].dtype) == "decimal(7,2)"
        out = q.to_pandas()
        assert list(out["m"]) == [Decimal("5.00"), Decimal("8.00")]

    def test_decimal_compare_float(self, session):
        df = _df(session, ["1.25", "2.00"])
        out = df.where(F.col("d") > 1.5).to_pandas()
        assert list(out["d"]) == [Decimal("2.00")]

    def test_decimal_in_list(self, session):
        df = _df(session, ["1.00", "2.00", "3.00"])
        out = df.where(F.col("d").isin([1, 3])).to_pandas()
        assert sorted(out["i"]) == [0, 2]

    def test_null_propagation(self, session):
        df = _df(session, ["1.00", None])
        q = df.select((F.col("d") + 1).alias("s"))
        assert str(q.schema.fields[0].dtype) == "decimal(6,2)"
        out = q.to_pandas()
        assert out["s"][0] == Decimal("2.00")
        assert out["s"][1] is None


class TestFirstLastIgnoreNulls:
    def _df(self, session):
        t = pa.table({
            "k": pa.array([1, 1, 1, 2, 2, 3]),
            "v": pa.array([None, 10, 20, None, None, 7], type=pa.int64()),
        })
        return session.create_dataframe(t)

    def test_first_ignore_nulls(self, session):
        df = self._df(session)
        out = df.group_by("k").agg(
            F.first(F.col("v"), ignore_nulls=True).alias("f")).to_pandas()
        got = dict(zip(out["k"], out["f"]))
        assert got[1] == 10
        assert got[2] is None or (got[2] != got[2])  # all-null group -> null
        assert got[3] == 7

    def test_last_ignore_nulls(self, session):
        df = self._df(session)
        out = df.group_by("k").agg(
            F.last(F.col("v"), ignore_nulls=True).alias("l")).to_pandas()
        got = dict(zip(out["k"], out["l"]))
        assert got[1] == 20
        assert got[3] == 7

    def test_first_keep_nulls(self, session):
        df = self._df(session)
        out = df.group_by("k").agg(
            F.first(F.col("v")).alias("f")).to_pandas()
        got = dict(zip(out["k"], out["f"]))
        # first row of group 1 is null
        assert got[1] is None or got[1] != got[1]
        assert got[3] == 7

    def test_ungrouped_first_ignore_nulls(self, session):
        t = pa.table({"v": pa.array([None, None, 5, 9], type=pa.int64())})
        df = session.create_dataframe(t)
        out = df.agg(F.first(F.col("v"), ignore_nulls=True).alias("f"),
                     F.last(F.col("v"), ignore_nulls=True).alias("l")
                     ).to_pandas()
        assert out["f"][0] == 5
        assert out["l"][0] == 9

    def test_first_across_batches_with_empty_batch(self, fresh_session):
        # multi-batch input where the FIRST batch is entirely filtered out:
        # the merge must not let the empty partial win with padding data
        fresh_session.conf.set("spark.rapids.tpu.sql.batchSizeRows", 4)
        import pandas as pd
        pdf = pd.DataFrame({
            "k": [0, 0, 0, 0, 1, 1, 1, 1],
            "v": [100, 101, 102, 103, 7, 8, 9, 10],
        })
        df = fresh_session.create_dataframe(pdf)
        out = (df.where(F.col("k") == 1)
                 .agg(F.first(F.col("v")).alias("f"),
                      F.last(F.col("v")).alias("l")).to_pandas())
        assert out["f"][0] == 7
        assert out["l"][0] == 10

    def test_first_all_rows_filtered(self, fresh_session):
        fresh_session.conf.set("spark.rapids.tpu.sql.batchSizeRows", 4)
        import pandas as pd
        pdf = pd.DataFrame({"k": [0] * 8, "v": list(range(8))})
        df = fresh_session.create_dataframe(pdf)
        out = (df.where(F.col("k") == 1)
                 .agg(F.first(F.col("v")).alias("f")).to_pandas())
        assert out["f"][0] is None or out["f"][0] != out["f"][0]


class TestWideDecimalSum:
    """SUM result precision min(38, p+10) with exact two-limb device
    accumulation + host reconstruction (TypeChecks.scala:626 DECIMAL_128,
    decimalExpressions.scala)."""

    def _table(self, vals, precision=15, scale=2):
        import decimal
        return pa.table({"k": pa.array([i % 3 for i in range(len(vals))],
                                       type=pa.int64()),
                         "d": pa.array(
            [None if v is None else decimal.Decimal(v) for v in vals],
            type=pa.decimal128(precision, scale))})

    def test_grouped_wide_sum_exact(self, fresh_session):
        import decimal
        sess = fresh_session
        from spark_rapids_tpu.sql import functions as F
        # values near the int64 edge: 9e12 each, 600 rows -> 5.4e15 per
        # group (scaled x100 = 5.4e17, summed exactly through the limbs)
        vals = ["9999999999999.99"] * 600
        df = (sess.create_dataframe(self._table(vals))
              .group_by("k").agg(F.sum(F.col("d")).alias("s")))
        got = dict(df.collect())
        each = decimal.Decimal("9999999999999.99")
        assert got[0] == each * 200
        assert got[1] == each * 200 and got[2] == each * 200

    def test_ungrouped_wide_sum(self, fresh_session):
        import decimal
        sess = fresh_session
        from spark_rapids_tpu.sql import functions as F
        vals = ["123456789012345.67", "-0.67", None]
        df = sess.create_dataframe(self._table(vals, precision=17)) \
            .agg(F.sum(F.col("d")).alias("s"))
        assert df.collect()[0][0] == decimal.Decimal("123456789012345.00")

    def test_result_precision_is_spark(self, fresh_session):
        sess = fresh_session
        from spark_rapids_tpu.sql import functions as F
        df = sess.create_dataframe(self._table(["1.00"])) \
            .agg(F.sum(F.col("d")).alias("s"))
        f = df.schema.fields[0]
        assert f.dtype.precision == 25 and f.dtype.scale == 2  # 15+10

    def test_two_phase_wide_sum(self, fresh_session):
        import decimal
        sess = fresh_session
        from spark_rapids_tpu.sql import functions as F
        sess.conf.set(
            "spark.rapids.tpu.sql.agg.singleProcessComplete", False)
        vals = ["8888888888888.88"] * 90
        df = (sess.create_dataframe(self._table(vals))
              .group_by("k").agg(F.sum(F.col("d")).alias("s")))
        got = dict(df.collect())
        assert got[0] == decimal.Decimal("8888888888888.88") * 30


class TestWideDecimalDevice:
    """Device decimal128 (VERDICT r4 item 6): 18 < p <= 38 columns ride
    as (capacity, 2) int64 limbs; add/subtract/compare/sum run ON DEVICE
    (ops/wide_decimal.py two-limb kernels — GpuCast.scala /
    DecimalUtil.scala analog) with exact results, asserted against
    python Decimal and with device placement verified via explain."""

    def _table(self, n=500, seed=7):
        import numpy as np
        rng = np.random.default_rng(seed)
        vals = [(Decimal(int(x)) * 31).scaleb(-2)
                for x in rng.integers(-10**18, 10**18, n)]
        vals[3] = None
        return pa.table({
            "a": pa.array(vals, type=pa.decimal128(25, 2)),
            "b": pa.array([Decimal("1.50")] * n, type=pa.decimal128(25, 2)),
            "k": pa.array(rng.integers(0, 5, n)),
        }), vals

    def test_wide_add_sub_on_device(self, session):
        from spark_rapids_tpu.sql import functions as F
        t, vals = self._table()
        df = session.create_dataframe(t)
        q = df.select((F.col("a") + F.col("b")).alias("s"),
                      (F.col("a") - F.col("b")).alias("d"))
        plan = q.explain_string()
        assert "!" not in plan.split("\n")[2], plan  # project on TPU
        got = q.collect()
        for (gs, gd), v in zip(got, vals):
            if v is None:
                assert gs is None and gd is None
            else:
                assert gs == v + Decimal("1.50")
                assert gd == v - Decimal("1.50")

    def test_wide_compare_filter(self, session):
        from spark_rapids_tpu.sql import functions as F
        t, vals = self._table()
        df = session.create_dataframe(t)
        got = df.filter(F.col("a") > F.col("b")).collect()
        assert len(got) == sum(1 for v in vals
                               if v is not None and v > Decimal("1.5"))
        got = df.filter(F.col("a") <= F.lit(Decimal("0.00"))).collect()
        assert len(got) == sum(1 for v in vals
                               if v is not None and v <= 0)

    def test_wide_grouped_sum_exact(self, session):
        import collections
        from spark_rapids_tpu.sql import functions as F
        t, vals = self._table()
        df = session.create_dataframe(t)
        got = df.group_by("k").agg(F.sum(F.col("a")).alias("s")).collect()
        w = collections.defaultdict(Decimal)
        for v, k in zip(vals, t.column("k").to_pylist()):
            if v is not None:
                w[k] += v
        assert dict((k, s) for k, s in got) == dict(w)

    def test_wide_ungrouped_sum_and_literal(self, session):
        from spark_rapids_tpu.sql import functions as F
        t, vals = self._table()
        df = session.create_dataframe(t)
        (got,), = df.agg(F.sum(F.col("a")).alias("s")).collect()
        assert got == sum(v for v in vals if v is not None)

    def test_wide_group_key_falls_back_correctly(self, session):
        # hash-grouping kernels are one-word: decimal128 GROUP BY keys
        # route to CPU (planner gate) and still compute exactly
        from spark_rapids_tpu.sql import functions as F
        t, vals = self._table(n=100)
        df = session.create_dataframe(t)
        q = df.group_by("b").agg(F.count_star().alias("c"))
        plan = q.explain_string()
        assert "decimal128 grouping keys" in plan
        got = q.collect()
        assert got == [(Decimal("1.50"), 100)]

    def test_zorder_by_date_column(self, session, tmp_path):
        import datetime
        import numpy as np
        import pyarrow as pa
        from spark_rapids_tpu.io.delta import delta_zorder, write_delta
        rng = np.random.default_rng(5)
        days = rng.integers(0, 3000, 2000)
        t = pa.table({
            "d": pa.array([datetime.date(1998, 1, 1)
                           + datetime.timedelta(days=int(x))
                           for x in days], type=pa.date32()),
            "x": rng.integers(0, 1000, 2000),
            "v": rng.uniform(0, 1, 2000)})
        path = str(tmp_path / "zd")
        write_delta(session.create_dataframe(t), path)
        before = sorted(session.read_delta(path).collect())
        delta_zorder(session, path, ["d", "x"], target_file_rows=500)
        after = sorted(session.read_delta(path).collect())
        assert after == before

"""TypeSig-driven tagging: declared expression signatures are enforced by
the planner, not just documented.

Reference: TypeChecks.scala:171 (TypeSig algebra), ExprChecks
(TypeChecks.scala:1125) — the same signature objects drive tagging AND
docs/supported_ops.md generation.
"""

import datetime

import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import types as T
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.types import TypeSig


class TestSigAlgebra:
    def test_check_and_describe(self):
        sig = TypeSig.numeric + TypeSig.null
        assert sig.check(T.INT64) is None
        assert sig.check(T.FLOAT64) is None
        assert "not supported" in sig.check(T.TIMESTAMP)
        assert "not supported" in sig.check(T.STRING)
        assert "decimal" in TypeSig.device_compute.check(T.decimal(38, 2))
        assert "int" in sig.describe() and "double" in sig.describe()
        # + - * and avg produce Spark's decimal types up to precision 38
        from spark_rapids_tpu import aggfns, exprs
        for cls in (exprs.Add, exprs.Subtract, exprs.Multiply,
                    aggfns.Average):
            assert cls.output_sig.check(T.decimal(38, 6)) is None, cls

    def test_add_subtract(self):
        s = TypeSig.common - TypeSig.string
        assert s.check(T.STRING) is not None
        assert s.check(T.INT32) is None


class TestSigDrivenTagging:
    def test_math_on_timestamp_falls_back_with_sig_reason(self, session):
        df = session.create_dataframe(
            {"ts": [datetime.datetime(2024, 1, 1)], "x": [4.0]})
        plan = df.select(F.sqrt(F.col("ts")).alias("s")).explain_string()
        assert "type timestamp is not supported" in plan
        assert "Sqrt input ts" in plan

    def test_math_on_double_stays_on_device(self, session):
        df = session.create_dataframe({"x": [4.0, 9.0]})
        q = df.select(F.sqrt(F.col("x")).alias("s"))
        plan = q.explain_string()
        assert "not supported" not in plan
        assert [r[0] for r in q.collect()] == [2.0, 3.0]

    def test_fallback_still_computes(self, session):
        """A sig rejection must fall back, not fail (RapidsMeta contract:
        tagged-no nodes run on CPU with reasons)."""
        df = session.create_dataframe(
            {"ts": [datetime.datetime(1970, 1, 1, 0, 0, 4)]})
        rows = df.select(F.sqrt(F.col("ts")).alias("s")).collect()
        assert len(rows) == 1  # value is CPU-path defined; shape matters


class TestDecimal128Tier:
    """decimal(18 < p <= 38) rides as DEVICE two-limb int64 columns
    (r5, ops/wide_decimal.py): projection/sort/add/compare/sum stay on
    device; only >38 or unsupported wide ops fall back."""

    def _df(self, session):
        import decimal

        import pyarrow as pa
        D = decimal.Decimal
        t = pa.table({
            "x": pa.array([D("99999999999999999999.50"), D("1.25"), None],
                          type=pa.decimal128(38, 2)),
            "y": [2.0, 3.0, 4.0]})
        return session.create_dataframe(t)

    def test_passthrough_projection_stays_on_device_plan(self, session):
        df = self._df(session)
        q = df.select("x", "y")
        assert "!" not in q.explain_string().splitlines()[2]
        rows = q.collect()
        assert [str(r[0]) for r in rows[:2]] == \
            ["99999999999999999999.50", "1.25"]

    def test_sort_key_on_device(self, session):
        # r5: decimal(38) rides as two int64 limbs — the sort contributes
        # (hi, lo-unsigned) operands and stays ON DEVICE
        import decimal
        df = self._df(session)
        q = df.sort("x")
        plan = q.explain_string()
        assert "host-carried column x" not in plan
        rows = q.collect()
        assert rows[0][0] is None  # nulls first (asc default)
        assert rows[1][0] == decimal.Decimal("1.25")
        assert rows[2][0] == decimal.Decimal("99999999999999999999.50")

    def test_wide_plus_float_on_device(self, session):
        # r5: decimal(38) + float promotes to float64 on device (lossy
        # like Spark's Decimal.toDouble) instead of CPU-falling-back
        from spark_rapids_tpu.sql import functions as F
        df = self._df(session)
        q = df.select((F.col("x") + F.col("y")).alias("z"))
        plan = q.explain_string()
        assert "!" not in plan.splitlines()[2], plan
        rows = q.collect()
        assert abs(rows[1][0] - 4.25) < 1e-9
        assert rows[2][0] is None


class TestSigsGenerateDocs:
    def test_supported_ops_include_sig_columns(self):
        from spark_rapids_tpu.docs import supported_ops_md
        md = supported_ops_md()
        assert "| Input types | Output types |" in md
        # Sqrt row shows its restricted numeric input / fp output sig
        row = next(ln for ln in md.splitlines() if ln.startswith("| Sqrt "))
        assert "decimal" in row and "float" in row
        assert "timestamp" not in row

"""Decimal arithmetic under Spark 3.x's rules (DecimalPrecision, with
allowPrecisionLoss on): the result type of ``+ - *``, ``sum`` and ``avg``,
and every value against Python's ``decimal`` at Spark's rounding (HALF_UP),
NULL past the result's precision and the raise under ANSI; and the
``ops/wide_decimal`` kernels against Python ints across their full range.
"""

import decimal
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.sql import functions as F

CTX = decimal.Context(prec=200, rounding=decimal.ROUND_HALF_UP)


def _values(p, s, n, rng):
    """Seeded decimal(p, s) values: the extremes, zero, and random ones of
    every length."""
    top = 10 ** p - 1
    ints = [top, -top, 0, 1, -1]
    for _ in range(n - len(ints)):
        digits = rng.integers(0, 10, int(rng.integers(1, p + 1)))
        v = int("".join(str(d) for d in digits))
        ints.append(v if rng.integers(0, 2) else -v)
    return [Decimal(f"{v}E-{s}") for v in ints]


def _spark(op, a, b, res):
    """The Spark 3.x value of ``a op b`` at ``res``: + and - cast both
    operands to the result type, * rounds the exact product; NULL past
    the result's precision."""
    q = Decimal(1).scaleb(-res.scale)
    if op == "*":
        v = CTX.multiply(a, b).quantize(q, context=CTX)
    else:
        a, b = a.quantize(q, context=CTX), b.quantize(q, context=CTX)
        v = CTX.add(a, b) if op == "+" else CTX.subtract(a, b)
    return None if abs(v) >= Decimal(10) ** (res.precision - res.scale) \
        else v


# (id, op, left decimal(p, s) or "lit:<int>", right, Spark's result type)
CASES = [
    ("q1_one_minus_discount", "-", "lit:1", (15, 2), (16, 2)),
    ("q1_one_plus_tax", "+", "lit:1", (15, 2), (16, 2)),
    ("q1_disc_price", "*", (15, 2), (16, 2), (32, 4)),
    ("q1_charge_loses_precision", "*", (32, 4), (16, 2), (38, 6)),
    ("q6_revenue", "*", (15, 2), (15, 2), (31, 4)),
    ("add_same", "+", (15, 2), (15, 2), (16, 2)),
    ("add_mixed_scales", "+", (5, 1), (5, 2), (7, 2)),
    ("sub_mixed_scales", "-", (10, 4), (12, 1), (16, 4)),
    ("add_narrow_to_wide", "+", (18, 2), (18, 2), (19, 2)),
    ("add_overflow_to_null", "+", (38, 2), (38, 2), (38, 2)),
    ("add_rounds_an_operand", "+", (20, 5), (38, 30), (38, 22)),
    ("sub_wide", "-", (30, 10), (25, 3), (33, 10)),
    ("mul_int_literal", "*", (15, 2), "lit:4", (17, 2)),
    ("mul_narrow", "*", (8, 3), (9, 2), (18, 5)),
    ("mul_rounds_and_overflows", "*", (38, 10), (38, 10), (38, 6)),
    ("mul_wide_by_wide", "*", (20, 2), (19, 4), (38, 6)),
    ("mul_128_by_64", "*", (22, 2), (16, 3), (38, 5)),
]


@pytest.mark.parametrize("name,op,left,right,want", CASES,
                         ids=[c[0] for c in CASES])
def test_arithmetic_type_and_values(session, name, op, left, right, want):
    rng = np.random.default_rng([len(name), ord(op)])
    cols, exprs, lits = {}, [], []
    for side, spec in (("l", left), ("r", right)):
        if isinstance(spec, str):
            lits.append(int(spec[4:]))
            exprs.append(int(spec[4:]))
        else:
            vals = _values(*spec, 40, rng)
            vals[7] = None
            cols[side] = pa.array(vals, type=pa.decimal128(*spec))
            exprs.append(F.col(side))
    df = session.create_dataframe(pa.table(cols))
    lhs, rhs = exprs
    out = {"+": lambda a, b: a + b, "-": lambda a, b: a - b,
           "*": lambda a, b: a * b}[op](lhs, rhs)
    q = df.select(out.alias("o"))
    assert str(q.schema.fields[0].dtype) == "decimal(%d,%d)" % want
    assert "!" not in q.explain_string().splitlines()[2]
    res = T.decimal(*want)
    got = [r[0] for r in q.collect()]
    arrays = [cols[s].to_pylist() if s in cols else None for s in "lr"]
    n = len(next(iter(cols.values())))
    for i in range(n):
        a = arrays[0][i] if arrays[0] is not None else Decimal(lits[0])
        b = arrays[1][i] if arrays[1] is not None else Decimal(lits[-1])
        w = None if a is None or b is None else _spark(op, a, b, res)
        assert got[i] == w, (i, a, b, got[i], w)
        if w is not None:  # the scale is the result type's
            assert got[i].as_tuple().exponent == -res.scale
    if name.endswith("overflow_to_null") or name.endswith("overflows"):
        assert any(g is None for g in got[:7])  # the extremes pass 38 digits


def test_overflow_raises_under_ansi(fresh_session):
    fresh_session.conf.set("spark.rapids.tpu.sql.ansi.enabled", True)
    top = Decimal(f"{10 ** 38 - 1}E-2")
    df = fresh_session.create_dataframe(pa.table({
        "a": pa.array([top, Decimal("1.00")], type=pa.decimal128(38, 2))}))
    with pytest.raises(ArithmeticError):
        df.select((F.col("a") + F.col("a")).alias("o")).collect()
    assert df.filter(F.col("a") < 5).select(
        (F.col("a") + F.col("a")).alias("o")).collect() == [
        (Decimal("2.00"),)]


AGG_CASES = [
    ("sum", (15, 2), (25, 2)),
    ("sum", (32, 4), (38, 4)),
    ("sum", (8, 0), (18, 0)),
    ("avg", (15, 2), (19, 6)),
    ("avg", (38, 6), (38, 10)),
    ("avg", (10, 0), (14, 4)),
    ("avg", (30, 10), (34, 14)),
]


@pytest.mark.parametrize("fn,src,want", AGG_CASES,
                         ids=[f"{f}_{p}_{s}" for f, (p, s), _ in AGG_CASES])
def test_aggregate_type_and_values(session, fn, src, want):
    rng = np.random.default_rng([src[0], src[1], len(fn)])
    # two digits short of the type: no sum of 60 passes its precision
    vals = _values(src[0] - 2, src[1], 60, rng)
    vals[3] = None
    keys = [i % 3 for i in range(len(vals))]
    df = session.create_dataframe(pa.table({
        "k": pa.array(keys, type=pa.int64()),
        "d": pa.array(vals, type=pa.decimal128(*src))}))
    f = F.sum if fn == "sum" else F.avg
    for q in (df.group_by("k").agg(f(F.col("d")).alias("o")),
              df.agg(f(F.col("d")).alias("o"))):
        assert str(q.schema.fields[-1].dtype) == "decimal(%d,%d)" % want
        assert "!" not in "".join(q.explain_string().splitlines()[2:])
        for row in q.collect():
            k = row[0] if len(row) == 2 else None
            xs = [v for v, kk in zip(vals, keys)
                  if v is not None and (k is None or kk == k)]
            total = Decimal(0)
            for x in xs:
                total = CTX.add(total, x)
            if fn == "sum":
                w = total.quantize(Decimal(1).scaleb(-want[1]), context=CTX)
            else:
                # Divide's scale, then the cast to the result: HALF_UP
                div = T.decimal_divide_type(T.decimal_sum_type(
                    T.decimal(*src)), T.decimal(20, 0))
                w = CTX.divide(total, Decimal(len(xs))).quantize(
                    Decimal(1).scaleb(-div.scale), context=CTX).quantize(
                    Decimal(1).scaleb(-want[1]), context=CTX)
                if abs(w) >= Decimal(10) ** (want[0] - want[1]):
                    w = None  # the cast to the result overflows: NULL
            assert row[-1] == w
            assert w is None or row[-1].as_tuple().exponent == -want[1]


@pytest.mark.parametrize("fn", ["sum", "avg"])
def test_a_sum_past_its_precision_is_null(session, fn):
    # the sums of group 0 pass 38 digits; group 1 fits avg's Divide at
    # decimal(38,6), the narrowest step (32 integer digits)
    big, fits = Decimal(f"{10 ** 37}E0"), Decimal(f"{10 ** 31}E0")
    df = session.create_dataframe(pa.table({
        "k": pa.array([0] * 20 + [1], type=pa.int64()),
        "d": pa.array([big] * 20 + [fits], type=pa.decimal128(38, 0))}))
    f = F.sum if fn == "sum" else F.avg
    got = dict(df.group_by("k").agg(f(F.col("d")).alias("o")).collect())
    assert got[0] is None and got[1] == fits


@pytest.mark.parametrize("p,s,want", [
    (39, 2, (38, 2)), (46, 23, (38, 15)), (49, 6, (38, 6)), (77, 20, (38, 6)),
    (38, 38, (38, 38)), (50, 40, (38, 28))])
def test_adjust_precision_scale(p, s, want):
    dt = T.adjust_precision_scale(p, s)
    assert (dt.precision, dt.scale) == want


# -- the kernels against python ints ------------------------------------------

def _limbs(vals):
    out = []
    for v in vals:
        u = v & ((1 << 128) - 1)
        lo, hi = u & ((1 << 64) - 1), u >> 64
        out.append([lo - (1 << 64) if lo >= 1 << 63 else lo,
                    hi - (1 << 64) if hi >= 1 << 63 else hi])
    return np.array(out, dtype=np.int64)


def _ints(arr):
    return [(int(h) << 64) + (int(lo) & ((1 << 64) - 1))
            for lo, h in np.asarray(arr)]


def _half_up(x, k):
    d = 10 ** k
    q = (abs(x) * 2 + d) // (2 * d)
    return q if x >= 0 else -q


def _operands(bits, n, rng):
    """Signed operands of ``bits`` bits (127 bits: up to +-(10^38 - 1)):
    the extremes, zero, +-1 and a carry across every 16-bit limb."""
    top = 10 ** 38 - 1 if bits == 128 else (1 << 63) - 1
    low = -top if bits == 128 else -(1 << 63)
    edge = [top, low, 0, 1, -1]
    edge += [(1 << (16 * k)) - 1 for k in range(1, bits // 16)]
    edge += [-(1 << (16 * k)) for k in range(1, bits // 16)]
    rand = [int(rng.integers(-2 ** 62, 2 ** 62)) * (
        int(rng.integers(1, 2 ** 62)) if bits == 128 else 1)
        for _ in range(n)]
    vals = edge + rand
    return [max(low, min(top, v)) for v in vals]


@pytest.mark.parametrize("shape", ["64x64", "128x64", "128x128"])
@pytest.mark.parametrize("drop", [0, 1, 6, 14])
def test_multiply_against_python_ints(shape, drop):
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import wide_decimal as wd
    rng = np.random.default_rng([drop, len(shape)])
    la, lb = (int(x) for x in shape.split("x"))
    a = _operands(la, 60, rng)
    b = _operands(lb, 60, rng)
    b = (b * (len(a) // len(b) + 1))[:len(a)]
    rng.shuffle(b)

    def dev(vals, bits):
        return jnp.asarray(_limbs(vals) if bits == 128
                           else np.array(vals, dtype=np.int64))
    out, over = wd.multiply(dev(a, la), dev(b, lb), drop)
    got, over = _ints(out), np.asarray(over)
    for x, y, g, o in zip(a, b, got, over):
        w = _half_up(x * y, drop)
        fits = -(1 << 127) <= w < (1 << 127)
        assert bool(o) == (not fits), (x, y, drop)
        if fits:
            assert g == w, (x, y, drop)


@pytest.mark.parametrize("k", [0, 1, 4, 5, 17, 37])
def test_half_up_down_scale_against_python_ints(k):
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import wide_decimal as wd
    rng = np.random.default_rng(k)
    a = _operands(128, 80, rng)
    # the ties: ...5 at the dropped digits, both signs
    a += [5 * 10 ** (k - 1), -5 * 10 ** (k - 1), 15 * 10 ** (k - 1)] if k \
        else []
    got = _ints(wd.round_pow10(jnp.asarray(_limbs(a)), k))
    assert got == [_half_up(x, k) for x in a]


def test_fits_against_python_ints():
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import wide_decimal as wd
    a = _operands(128, 40, np.random.default_rng(3)) + [10 ** 20, -10 ** 20]
    limbs = jnp.asarray(_limbs(a))
    for p in (18, 20, 38):
        assert list(np.asarray(wd.fits(limbs, p))) == [
            abs(x) < 10 ** p for x in a]
    small = [(1 << 63) - 1, -(1 << 63), 10 ** 18, -(10 ** 18) + 1]
    assert list(np.asarray(wd.fits(jnp.asarray(np.array(
        small, dtype=np.int64)), 18))) == [abs(x) < 10 ** 18 for x in small]

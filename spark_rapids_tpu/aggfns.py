"""Aggregate function declarations (SUM/COUNT/MIN/MAX/AVG/FIRST/LAST...).

TPU-native analog of the reference's ``GpuAggregateFunction`` hierarchy
(org/apache/spark/sql/rapids/AggregateFunctions.scala): each function declares
its *update* contributions, its reduction buffers, and a *finalize* step.  The
reference maps these to cuDF group-by aggregations; here they map to masked
XLA segment reductions (ops/groupby.py) — sort-based grouping being the
TPU-idiomatic choice (SURVEY.md §7.3 "hash tables").

An aggregate is described by parallel lists:
  * ``buffers()``  → list of (dtype, reduce_op) with reduce_op ∈
    {"sum", "min", "max", "first", "last"}
  * ``update(ctx)`` → per-row contribution Values, one per buffer
  * ``finalize(values)`` → final (data, valid) from reduced buffers

Partial/merge mode (two-phase aggregation across batches or shuffle) reuses
the same reduce_op on the buffer columns, exactly like Spark's partial/final
agg split.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import types as T
from .exprs import AggregateExpression, EvalContext, Expression, Value

__all__ = ["Sum", "Count", "CountStar", "Min", "Max", "Average", "First", "Last",
           "VariancePop", "VarianceSamp", "StddevPop", "StddevSamp",
           "CovarPop", "CovarSamp", "Corr", "Percentile",
           "ApproxPercentile", "AGG_CLASSES"]


def _ones(ctx: EvalContext):
    return jnp.ones((ctx.capacity,), dtype=jnp.int64)


def _valid_indicator(v: Optional[jax.Array], ctx: EvalContext) -> jax.Array:
    if v is None:
        return _ones(ctx)
    return v.astype(jnp.int64)


def _decimal_sum_lanes(d, v, ctx, wide_in: bool) -> List[Value]:
    """The int64 lanes of an exact decimal sum, the valid count last.  A
    narrow input (scaled int64) splits into lo 32 bits and the signed rest;
    a wide one (two limbs) into four carry-free 32-bit chunks (lo0, lo1,
    hi0, hi1-signed).  Every lane sum stays under 2^63 for up to 2^31 rows,
    so the host rebuilds the total exactly whatever cancels."""
    if wide_in:
        lo, hi = d[..., 0], d[..., 1]
        if v is not None:
            z = jnp.zeros_like(lo)
            lo = jnp.where(v, lo, z)
            hi = jnp.where(v, hi, z)
        m32 = np.int64(0xFFFFFFFF)
        l0 = lo & m32
        l1 = jax.lax.shift_right_logical(lo, np.int64(32))
        h0 = hi & m32
        h1 = hi >> np.int64(32)  # arithmetic: keeps the sign
        return [(l0, None), (l1, None), (h0, None), (h1, None),
                (_valid_indicator(v, ctx), None)]
    d = d.astype(jnp.int64)
    if v is not None:
        d = jnp.where(v, d, jnp.zeros_like(d))
    hi = d >> np.int64(32)
    lo = d - (hi << np.int64(32))  # in [0, 2^32)
    return [(lo, None), (hi, None), (_valid_indicator(v, ctx), None)]


def _decimal_sum_buffers(wide_in: bool):
    return [(T.INT64, "sum")] * (5 if wide_in else 3)


def _decimal_sums_host(buffers, n_rows: int, wide_in: bool):
    """(positions of the groups that hold rows, their python-int totals,
    their counts) among the first ``n_rows`` groups, from the lanes of
    :func:`_decimal_sum_lanes` (host arrays or device ones).  A grid's
    output holds its few groups among capacity slots: the empty slots
    cost no python."""
    lanes = [np.asarray(b[0][:n_rows]) for b in buffers]
    live = np.flatnonzero(lanes[-1] > 0)
    lanes = [lane[live] for lane in lanes]
    if wide_in:
        l0, l1, h0, h1, cnt = lanes
        return live, ((h1.astype(object) << 96) + (h0.astype(object) << 64)
                      + (l1.astype(object) << 32) + l0.astype(object)), cnt
    lo, hi, cnt = lanes
    return live, (hi.astype(object) << 32) + lo.astype(object), cnt


def _half_up_div(num: int, den: int) -> int:
    """num / den rounded HALF_UP (ties away from zero), exactly."""
    q = (abs(num) * 2 + den) // (2 * den)
    return q if num >= 0 else -q


def _decimal_array(values, dtype):
    """Python ints at ``dtype``'s scale (None for NULL) -> arrow decimal128."""
    import pyarrow as pa
    return pa.array([None if x is None else T.decimal_value(x, dtype.scale)
                     for x in values],
                    type=pa.decimal128(dtype.precision, dtype.scale))


def _overflow(totals, dtype, ansi: bool, what: str):
    """Groups whose total passes ``dtype``'s precision: NULL, or the raise
    under ANSI."""
    bound = 10 ** dtype.precision
    over = np.array([abs(t) >= bound for t in totals], dtype=bool)
    if ansi and over.any():
        raise OverflowError(
            f"{what} overflowed decimal({dtype.precision},{dtype.scale}) "
            "(ANSI mode)")
    return over


class Sum(AggregateExpression):
    """SUM with Spark result types: decimal(p,s) → decimal(min(38,p+10),s)
    (TypeChecks.scala:626 DECIMAL_128, decimalExpressions.scala).  Wide
    results (precision > 18) accumulate ON DEVICE as int64 lanes of the
    scaled value (:func:`_decimal_sum_lanes`) and reconstruct EXACTLY on
    the host at finalize — python ints are arbitrary precision, so no
    128-bit device kernel is needed; overflow past the result precision
    raises under ANSI, else NULL."""

    func = "sum"
    input_sig = (T.TypeSig.device_compute
                 + T.TypeSig((T.TypeKind.DECIMAL,),
                             max_decimal_precision=38))
    output_sig = (T.TypeSig.device_compute
                  + T.TypeSig((T.TypeKind.DECIMAL,),
                              max_decimal_precision=38))

    def _resolve(self):
        c = self.children[0].dtype
        self._wide = False
        self._wide_in = False
        if c.is_integral or c.kind == T.TypeKind.BOOLEAN:
            self.dtype = T.INT64
        elif c.is_floating:
            self.dtype = T.FLOAT64
        elif c.is_decimal:
            self.dtype = T.decimal_sum_type(c)
            self._wide = self.dtype.precision > 18
            self._wide_in = c.is_wide_decimal
        else:
            raise TypeError(f"sum of {c} not supported")
        self.nullable = True

    @property
    def host_finalize(self) -> bool:
        return getattr(self, "_wide", False)

    def buffers(self):
        if getattr(self, "_wide", False):
            return _decimal_sum_buffers(self._wide_in)
        return [(self.dtype, "sum"), (T.INT64, "sum")]

    def update(self, ctx) -> List[Value]:
        d, v = self.children[0].eval(ctx)
        if getattr(self, "_wide", False):
            return _decimal_sum_lanes(d, v, ctx, self._wide_in)
        d = d.astype(self.dtype.numpy_dtype)
        if v is not None:
            d = jnp.where(v, d, jnp.zeros_like(d))
        return [(d, None), (_valid_indicator(v, ctx), None)]

    def finalize(self, values: List[Value]) -> Value:
        (s, _), (cnt, _) = values
        return s, cnt > 0

    def finalize_host(self, buffers, n_rows: int, ansi: bool):
        """Exact host reconstruction of wide sums: arrow decimal128."""
        live, totals, _ = _decimal_sums_host(buffers, n_rows, self._wide_in)
        over = _overflow(totals, self.dtype, ansi, "sum")
        out = [None] * n_rows
        for i, t, o in zip(live, totals, over):
            if not o:
                out[i] = int(t)
        return _decimal_array(out, self.dtype)


class Count(AggregateExpression):
    func = "count"

    def _resolve(self):
        self.dtype = T.INT64
        self.nullable = False

    def buffers(self):
        return [(T.INT64, "sum")]

    def update(self, ctx):
        _, v = self.children[0].eval(ctx)
        return [(_valid_indicator(v, ctx), None)]

    def finalize(self, values):
        return values[0][0], None


class CountStar(AggregateExpression):
    func = "count(*)"

    def __init__(self):
        super().__init__(None)
        self.dtype = T.INT64
        self.nullable = False

    def buffers(self):
        return [(T.INT64, "sum")]

    def update(self, ctx):
        return [(_ones(ctx), None)]

    def finalize(self, values):
        return values[0][0], None


class _MinMax(AggregateExpression):
    reduce_op = "?"

    def _resolve(self):
        self.dtype = self.children[0].dtype
        self.nullable = True

    def buffers(self):
        return [(self.dtype, self.reduce_op), (T.INT64, "sum")]

    def update(self, ctx):
        d, v = self.children[0].eval(ctx)
        return [(d, v), (_valid_indicator(v, ctx), None)]

    def finalize(self, values):
        (m, _), (cnt, _) = values
        return m, cnt > 0


class Min(_MinMax):
    func = "min"
    reduce_op = "min"


class Max(_MinMax):
    func = "max"
    reduce_op = "max"


class Average(AggregateExpression):
    """AVG: int/float -> double over (sum, count).  A decimal(p,s) ->
    decimal(min(38,p+4), min(38,s+4)), exact, as Spark's Average does: the
    sum (decimal(min(38,p+10),s), NULL past it) divided by the count as
    decimal(20,0) at Divide's type, then cast to the result, HALF_UP at each
    step.  The sum rides as :class:`Sum`'s int64 lanes and the division
    runs on the host, in python ints, where the groups are few."""

    func = "avg"
    input_sig = Sum.input_sig
    output_sig = Sum.output_sig

    def _resolve(self):
        c = self.children[0].dtype
        self._decimal = c.is_decimal
        self.dtype = T.decimal_avg_type(c) if c.is_decimal else T.FLOAT64
        self.nullable = True

    @property
    def host_finalize(self) -> bool:
        return getattr(self, "_decimal", False)

    def buffers(self):
        if getattr(self, "_decimal", False):
            return _decimal_sum_buffers(self.children[0].dtype.is_wide_decimal)
        return [(T.FLOAT64, "sum"), (T.INT64, "sum")]

    def update(self, ctx):
        d, v = self.children[0].eval(ctx)
        src = self.children[0].dtype
        if getattr(self, "_decimal", False):
            return _decimal_sum_lanes(d, v, ctx, src.is_wide_decimal)
        d = d.astype(jnp.float64)
        if v is not None:
            d = jnp.where(v, d, jnp.zeros_like(d))
        return [(d, None), (_valid_indicator(v, ctx), None)]

    def finalize(self, values):
        (s, _), (cnt, _) = values
        ok = cnt > 0
        return s / jnp.where(ok, cnt, 1).astype(jnp.float64), ok

    def decimal_value(self, total: int, n: int) -> Optional[int]:
        """The average of ``n`` values summing to ``total`` (python ints at
        the input's scale), at the result's scale; None for NULL."""
        sum_t = T.decimal_sum_type(self.children[0].dtype)
        div_t = T.decimal_divide_type(sum_t, T.decimal(20, 0))
        res = self.dtype
        if n <= 0 or abs(total) >= 10 ** sum_t.precision:
            return None
        # Divide: sum / count at div_t's scale, HALF_UP
        q = _half_up_div(total * 10 ** (div_t.scale - sum_t.scale), n)
        if abs(q) >= 10 ** div_t.precision:
            return None
        # Cast to the result type, HALF_UP
        k = div_t.scale - res.scale
        q = _half_up_div(q, 10 ** k) if k >= 0 else q * 10 ** -k
        return None if abs(q) >= 10 ** res.precision else q

    def finalize_host(self, buffers, n_rows: int, ansi: bool):
        src = self.children[0].dtype
        live, totals, cnt = _decimal_sums_host(buffers, n_rows,
                                               src.is_wide_decimal)
        _overflow(totals, T.decimal_sum_type(src), ansi, "avg's sum")
        out = [None] * n_rows
        for i, t, n in zip(live, totals, cnt):
            out[i] = self.decimal_value(int(t), int(n))
        return _decimal_array(out, self.dtype)


class First(AggregateExpression):
    func = "first"
    reduce_choice = "first"

    def __init__(self, child: Expression, ignore_nulls: bool = False):
        self.ignore_nulls = ignore_nulls
        super().__init__(child)

    def _resolve(self):
        self.dtype = self.children[0].dtype
        self.nullable = True

    def buffers(self):
        if self.ignore_nulls:
            # single buffer: the first_valid/last_valid reduction yields both
            # the value and whether any non-null row existed
            return [(self.dtype, f"{self.reduce_choice}_valid")]
        # value + validity carried through first/last reduction
        return [(self.dtype, self.reduce_choice), (T.INT64, self.reduce_choice)]

    def update(self, ctx):
        d, v = self.children[0].eval(ctx)
        if self.ignore_nulls:
            return [(d, v)]
        return [(d, v), (_valid_indicator(v, ctx), None)]

    def finalize(self, values):
        if self.ignore_nulls:
            d, v = values[0]
            return d, v
        (d, _), (vi, vh) = values
        # vi>0 = the picked row was non-null; vh (when present) = some batch
        # actually had an active row (guards the all-filtered-input case)
        ok = vi > 0
        if vh is not None:
            ok = ok & vh
        return d, ok

    def _fp_extra(self):
        return f"{self.func}:{self.dtype}:ign={self.ignore_nulls}"


class Last(First):
    func = "last"
    reduce_choice = "last"


class _CentralMoment(AggregateExpression):
    """Variance/stddev via (n, Σx, Σx²) sum buffers.

    The reference merges Welford M2 partials (AggregateFunctions.scala M2);
    M2 merging is not a plain segment-sum, so the TPU shape is the
    sum-of-squares formulation — numerically adequate in float64 and it
    rides the existing "sum" reduction everywhere (batch merge, exchange,
    re-partition) with zero new machinery.
    """

    sample = False
    sqrt = False

    def _resolve(self):
        self.dtype = T.FLOAT64
        self.nullable = True

    def buffers(self):
        return [(T.INT64, "sum"), (T.FLOAT64, "sum"), (T.FLOAT64, "sum")]

    def update(self, ctx):
        d, v = self.children[0].eval(ctx)
        src = self.children[0].dtype
        x = d.astype(jnp.float64)
        if src.is_decimal:
            x = x / (10.0 ** src.scale)
        if v is not None:
            x = jnp.where(v, x, 0.0)
        return [(_valid_indicator(v, ctx), None), (x, None), (x * x, None)]

    def finalize(self, values):
        (n, _), (sx, _), (sxx, _) = values
        nf = n.astype(jnp.float64)
        ok = n > 0
        safe_n = jnp.where(ok, nf, 1.0)
        m2 = jnp.maximum(sxx - sx * sx / safe_n, 0.0)  # clamp fp negatives
        if self.sample:
            # n==1 → NULL (Spark 3.1+ default, legacy.statisticalAggregate
            # off — Spark returns NaN only under the legacy flag)
            ok = n > 1
            var = m2 / jnp.maximum(nf - 1.0, 1.0)
        else:
            var = m2 / safe_n
        out = jnp.sqrt(var) if self.sqrt else var
        return out, ok


class VariancePop(_CentralMoment):
    func = "var_pop"


class VarianceSamp(_CentralMoment):
    func = "var_samp"
    sample = True


class StddevPop(_CentralMoment):
    func = "stddev_pop"
    sqrt = True


class StddevSamp(_CentralMoment):
    func = "stddev_samp"
    sample = True
    sqrt = True


class _BinaryAgg(AggregateExpression):
    """Two-child aggregate (corr / covar family)."""

    def __init__(self, left: Expression, right: Expression):
        self.children = (left, right)
        if left.resolved() and right.resolved():
            self._resolve()

    def _resolve(self):
        self.dtype = T.FLOAT64
        self.nullable = True

    def _xy(self, ctx):
        xd, xv = self.children[0].eval(ctx)
        yd, yv = self.children[1].eval(ctx)

        def f64(d, e):
            d = d.astype(jnp.float64)
            if e.dtype.is_decimal:
                d = d / (10.0 ** e.dtype.scale)
            return d

        x, y = f64(xd, self.children[0]), f64(yd, self.children[1])
        if xv is None and yv is None:
            both = None
        else:
            both = (xv if xv is not None else jnp.ones_like(x, dtype=bool))
            if yv is not None:
                both = both & yv
        if both is not None:
            x = jnp.where(both, x, 0.0)
            y = jnp.where(both, y, 0.0)
        return x, y, both


class _Covariance(_BinaryAgg):
    """covar_pop / covar_samp via (n, Σx, Σy, Σxy)."""

    sample = False

    def buffers(self):
        return [(T.INT64, "sum"), (T.FLOAT64, "sum"), (T.FLOAT64, "sum"),
                (T.FLOAT64, "sum")]

    def update(self, ctx):
        x, y, both = self._xy(ctx)
        ind = _valid_indicator(both, ctx)
        return [(ind, None), (x, None), (y, None), (x * y, None)]

    def finalize(self, values):
        (n, _), (sx, _), (sy, _), (sxy, _) = values
        nf = n.astype(jnp.float64)
        ok = n > 0
        safe_n = jnp.where(ok, nf, 1.0)
        c = sxy - sx * sy / safe_n
        if self.sample:
            ok = n > 1  # NULL for n<2 (non-legacy Spark)
            out = c / jnp.maximum(nf - 1.0, 1.0)
        else:
            out = c / safe_n
        return out, ok


class CovarPop(_Covariance):
    func = "covar_pop"


class CovarSamp(_Covariance):
    func = "covar_samp"
    sample = True


class Corr(_BinaryAgg):
    """Pearson correlation via (n, Σx, Σy, Σxy, Σx², Σy²)."""

    func = "corr"

    def buffers(self):
        return [(T.INT64, "sum")] + [(T.FLOAT64, "sum")] * 5

    def update(self, ctx):
        x, y, both = self._xy(ctx)
        ind = _valid_indicator(both, ctx)
        return [(ind, None), (x, None), (y, None), (x * y, None),
                (x * x, None), (y * y, None)]

    def finalize(self, values):
        (n, _), (sx, _), (sy, _), (sxy, _), (sxx, _), (syy, _) = values
        nf = n.astype(jnp.float64)
        ok = n > 1  # corr of <2 points is NULL (non-legacy Spark)
        safe_n = jnp.where(n > 0, nf, 1.0)
        cov = sxy - sx * sy / safe_n
        vx = jnp.maximum(sxx - sx * sx / safe_n, 0.0)
        vy = jnp.maximum(syy - sy * sy / safe_n, 0.0)
        denom = jnp.sqrt(vx * vy)
        out = jnp.where(denom > 0, cov / jnp.where(denom > 0, denom, 1.0),
                        jnp.nan)
        return out, ok


class Percentile(AggregateExpression):
    """Exact percentile with linear interpolation (Spark ``percentile``).

    Needs every group's values materialized — not expressible as fixed
    reduction buffers, so it runs on the CPU operator (the reference's
    GpuApproximatePercentile uses t-digest sketches; an exact sort-based
    device version is the planned TPU shape).
    """

    func = "percentile"
    device_supported = False

    def __init__(self, child: Expression, q: float):
        self.q = float(q)
        super().__init__(child)

    def _resolve(self):
        self.dtype = T.FLOAT64
        self.nullable = True

    def _fp_extra(self):
        return f"{self.func}:{self.q}:{self.dtype}"


class ApproxPercentile(AggregateExpression):
    """approx_percentile via a MOMENTS SKETCH (Gan et al., SIGMOD'18):
    buffers = [n, Σx, Σx², Σx³, Σx⁴, min, max] — every one reduces with
    sum/min/max, so the sketch merges through the two-phase exchange
    exactly like the reference's t-digest buffers
    (GpuApproximatePercentile.scala).  finalize estimates the quantile
    with a Cornish-Fisher expansion from the standardized moments,
    clamped to the observed [min, max].  Accuracy is distributional (good
    for smooth data), not rank-bounded like t-digest — documented in
    supported_ops.
    """

    func = "approx_percentile"

    def __init__(self, child: Expression, q: float, accuracy: int = 10000):
        self.q = float(q)
        self.accuracy = int(accuracy)
        super().__init__(child)

    def _resolve(self):
        self.dtype = T.FLOAT64
        self.nullable = True

    def _fp_extra(self):
        return f"{self.func}:{self.q}:{self.dtype}"

    def buffers(self):
        return [(T.FLOAT64, "sum"), (T.FLOAT64, "sum"), (T.FLOAT64, "sum"),
                (T.FLOAT64, "sum"), (T.FLOAT64, "sum"),
                (T.FLOAT64, "min"), (T.FLOAT64, "max")]

    def update(self, ctx) -> List[Value]:
        d, v = self.children[0].eval(ctx)
        x = d.astype(jnp.float64)
        if self.children[0].dtype.is_decimal:
            x = x / (10.0 ** self.children[0].dtype.scale)
        m = _valid_indicator(v, ctx)
        mf = m.astype(jnp.float64)
        xz = jnp.where(m, x, 0.0)
        return [
            (mf, None), (xz, None), (xz * xz, None),
            (xz * xz * xz, None), (xz * xz * xz * xz, None),
            (x, v), (x, v),
        ]

    def finalize(self, values: List[Value]) -> Value:
        (n, _), (s1, _), (s2, _), (s3, _), (s4, _), (mn, mnv), (mx, mxv) \
            = values
        has = n > 0
        nn = jnp.where(has, n, 1.0)
        mean = s1 / nn
        var = jnp.maximum(s2 / nn - mean * mean, 0.0)
        sd = jnp.sqrt(var)
        sd_safe = jnp.where(sd > 0, sd, 1.0)
        m3 = s3 / nn - 3 * mean * s2 / nn + 2 * mean ** 3
        m4 = (s4 / nn - 4 * mean * s3 / nn + 6 * mean ** 2 * s2 / nn
              - 3 * mean ** 4)
        skew = jnp.where(sd > 0, m3 / sd_safe ** 3, 0.0)
        kurt = jnp.where(sd > 0, m4 / sd_safe ** 4 - 3.0, 0.0)
        # Cornish-Fisher: z adjusted by skewness and excess kurtosis
        from jax.scipy.stats import norm
        z = norm.ppf(jnp.clip(self.q, 1e-9, 1 - 1e-9))
        zc = (z + (z * z - 1) * skew / 6.0
              + (z ** 3 - 3 * z) * kurt / 24.0
              - (2 * z ** 3 - 5 * z) * (skew ** 2) / 36.0)
        est = mean + sd * zc
        est = jnp.clip(est, mn, mx)
        valid = has if mnv is None else (has & mnv)
        return est, valid


class CollectList(AggregateExpression):
    """collect_list: group values into an ARRAY column (AggregateFunctions
    .scala GpuCollectList).  Like Percentile it needs materialized groups —
    runs on the CPU operator; the result rides as a host arrow list
    column."""

    func = "collect_list"
    device_supported = False

    def _resolve(self):
        self.dtype = T.array(self.children[0].dtype)
        self.nullable = False  # empty group → empty array, like Spark

    def _fp_extra(self):
        return f"{self.func}:{self.dtype}"


class CollectSet(CollectList):
    """collect_set: distinct values per group (order unspecified)."""

    func = "collect_set"


AGG_CLASSES = {c.func: c for c in
               [Sum, Count, CountStar, Min, Max, Average, First, Last,
                VariancePop, VarianceSamp, StddevPop, StddevSamp,
                CovarPop, CovarSamp, Corr, Percentile, ApproxPercentile,
                CollectList,
                CollectSet]}

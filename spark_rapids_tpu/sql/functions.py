"""pyspark.sql.functions-style builder functions."""

from __future__ import annotations

from typing import Any, Optional

from .. import aggfns as A
from .. import exprs as E
from .. import types as T
from .column import Column, to_expr

__all__ = [
    "broadcast",
    "array", "struct", "element_at", "size", "array_contains",
    "sort_array", "array_distinct", "array_min", "array_max",
    "array_position", "slice", "flatten", "array_join", "array_union",
    "array_intersect", "array_except", "get_json_object", "from_json",
    "to_json",
    "col", "lit", "when", "coalesce", "isnull", "isnan", "expr_abs",
    "sum", "count", "count_star", "min", "max", "avg", "mean", "first", "last",
    "grouping", "grouping_id",
    "row_number", "rank", "dense_rank", "percent_rank", "cume_dist", "ntile",
    "lag", "lead", "parse_type",
    # math
    "sqrt", "cbrt", "exp", "expm1", "log", "log10", "log2", "log1p",
    "sin", "cos", "tan", "asin", "acos", "atan", "sinh", "cosh", "tanh",
    "degrees", "radians", "signum", "floor", "ceil", "round", "bround",
    "pow", "atan2", "hypot", "greatest", "least",
    # datetime
    "year", "month", "dayofmonth", "quarter", "dayofweek", "weekday",
    "dayofyear", "weekofyear", "last_day", "date_add", "date_sub",
    "datediff", "add_months", "months_between", "trunc",
    # string
    "length", "upper", "lower", "reverse", "initcap", "trim", "ltrim",
    "rtrim", "substring", "concat", "concat_ws", "startswith", "endswith",
    "contains", "like", "rlike", "regexp_extract", "regexp_replace",
    "replace", "lpad", "rpad", "repeat", "locate", "instr",
    "substring_index",
    # statistical aggregates
    "stddev", "stddev_samp", "stddev_pop", "variance", "var_samp",
    "var_pop", "corr", "covar_pop", "covar_samp", "percentile",
    "percentile_approx",
    # bitwise / hash
    "bitwise_not", "bitwiseNOT", "shiftleft", "shiftright",
    "shiftrightunsigned", "hash", "xxhash64",
]

def col(name: str) -> Column:
    return Column(E.UnresolvedColumn(name))


def monotonically_increasing_id() -> Column:
    """int64 (partition_id << 33) + row_position — unique and
    increasing, not consecutive (GpuMonotonicallyIncreasingID)."""
    from ..miscfns import MonotonicallyIncreasingID
    return Column(MonotonicallyIncreasingID())


def spark_partition_id() -> Column:
    from ..miscfns import SparkPartitionID
    return Column(SparkPartitionID())


def input_file_name() -> Column:
    """The file backing the current batch, '' when not directly above a
    file scan (GpuInputFileName + InputFileBlockRule degradation)."""
    from ..miscfns import InputFileName
    return Column(InputFileName())


def scalar_subquery(df) -> Column:
    """A 1x1 subquery as an expression: executed at collect() time
    (recursively) and substituted as a literal — GpuScalarSubquery
    analog (plan/subquery.py)."""
    from ..plan.subquery import ScalarSubquery
    return Column(ScalarSubquery(df._plan))


def broadcast(df):
    """Hint that ``df`` should be broadcast in joins (pyspark
    functions.broadcast analog; GpuBroadcastHashJoinExecBase selection)."""
    return df.hint("broadcast")





# -- collections / nested types (complexTypeCreator / collectionOperations) --

def array(*cols) -> Column:
    from .. import collectionfns as C
    return Column(C.CreateArray(*[to_expr(c) for c in cols]))


def struct(*cols) -> Column:
    from .. import collectionfns as C
    names = [getattr(c, "name", None) or f"col{i + 1}"
             for i, c in enumerate(cols)]
    return Column(C.CreateStruct(names, *[to_expr(c) for c in cols]))


def element_at(col_, idx) -> Column:
    from .. import collectionfns as C
    from .. import types as T
    e = to_expr(col_)
    if e.dtype is not None and e.dtype.kind == T.TypeKind.MAP:
        return Column(C.GetMapValue(e, to_expr(idx)))
    return Column(C.ElementAt(e, to_expr(idx)))


def _lambda_body(fn, *var_names):
    """Invoke a python lambda with reserved-variable Columns; returns the
    body expression (higherOrderFunctions.scala lambda capture)."""
    from .. import collectionfns as C
    import inspect
    n_args = len(inspect.signature(fn).parameters)
    cols = [Column(E.UnresolvedColumn(v)) for v in var_names[:n_args]]
    return to_expr(fn(*cols))


def transform(col_, fn) -> Column:
    """transform(arr, x -> f(x)) or (x, i) -> f(x, i)
    (GpuArrayTransform, higherOrderFunctions.scala:291)."""
    from .. import collectionfns as C
    body = _lambda_body(fn, C.HOF_X, C.HOF_I)
    return Column(C.ArrayTransform(to_expr(col_), body=body))


def filter(col_, fn) -> Column:  # noqa: A001 — pyspark name
    from .. import collectionfns as C
    body = _lambda_body(fn, C.HOF_X, C.HOF_I)
    return Column(C.ArrayFilter(to_expr(col_), body=body))


array_filter = filter


def exists(col_, fn) -> Column:
    from .. import collectionfns as C
    return Column(C.ArrayExists(to_expr(col_),
                                body=_lambda_body(fn, C.HOF_X)))


def forall(col_, fn) -> Column:
    from .. import collectionfns as C
    return Column(C.ArrayForAll(to_expr(col_),
                                body=_lambda_body(fn, C.HOF_X)))


def aggregate(col_, zero, merge, finish=None) -> Column:
    """aggregate(arr, zero, (acc, x) -> merge[, acc -> finish])."""
    from .. import collectionfns as C
    body = _lambda_body(merge, C.HOF_ACC, C.HOF_X)
    fin = _lambda_body(finish, C.HOF_ACC) if finish is not None else None
    return Column(C.ArrayAggregate(to_expr(col_), to_expr(zero),
                                   body=body, finish=fin))


reduce = aggregate


def zip_with(left, right, fn) -> Column:
    from .. import collectionfns as C
    body = _lambda_body(fn, C.HOF_X, C.HOF_Y)
    return Column(C.ZipWith(to_expr(left), to_expr(right), body=body))


def create_map(*cols) -> Column:
    from .. import collectionfns as C
    return Column(C.CreateMap(*[to_expr(c) for c in cols]))


def map_keys(col_) -> Column:
    from .. import collectionfns as C
    return Column(C.MapKeys(to_expr(col_)))


def map_values(col_) -> Column:
    from .. import collectionfns as C
    return Column(C.MapValues(to_expr(col_)))


def map_entries(col_) -> Column:
    from .. import collectionfns as C
    return Column(C.MapEntries(to_expr(col_)))


def map_from_arrays(keys, values) -> Column:
    from .. import collectionfns as C
    return Column(C.MapFromArrays(to_expr(keys), to_expr(values)))


def map_from_entries(col_) -> Column:
    from .. import collectionfns as C
    return Column(C.MapFromEntries(to_expr(col_)))


def map_concat(*cols) -> Column:
    from .. import collectionfns as C
    return Column(C.MapConcat(*[to_expr(c) for c in cols]))


def map_filter(col_, fn) -> Column:
    from .. import collectionfns as C
    body = _lambda_body(fn, C.HOF_X, C.HOF_Y)
    return Column(C.MapFilter(to_expr(col_), body=body))


def transform_keys(col_, fn) -> Column:
    from .. import collectionfns as C
    body = _lambda_body(fn, C.HOF_X, C.HOF_Y)
    return Column(C.TransformKeys(to_expr(col_), body=body))


def transform_values(col_, fn) -> Column:
    from .. import collectionfns as C
    body = _lambda_body(fn, C.HOF_X, C.HOF_Y)
    return Column(C.TransformValues(to_expr(col_), body=body))


def size(col_) -> Column:
    from .. import collectionfns as C
    return Column(C.Size(to_expr(col_)))


def array_contains(col_, value) -> Column:
    from .. import collectionfns as C
    return Column(C.ArrayContains(to_expr(col_), to_expr(value)))


def sort_array(col_, asc: bool = True) -> Column:
    from .. import collectionfns as C
    return Column(C.SortArray(to_expr(col_), asc))


def array_distinct(col_) -> Column:
    from .. import collectionfns as C
    return Column(C.ArrayDistinct(to_expr(col_)))


def array_min(col_) -> Column:
    from .. import collectionfns as C
    return Column(C.ArrayMin(to_expr(col_)))


def array_max(col_) -> Column:
    from .. import collectionfns as C
    return Column(C.ArrayMax(to_expr(col_)))


def array_position(col_, value) -> Column:
    from .. import collectionfns as C
    return Column(C.ArrayPosition(to_expr(col_), to_expr(value)))


def slice(col_, start, length) -> Column:  # noqa: A001 — pyspark naming
    from .. import collectionfns as C
    return Column(C.Slice(to_expr(col_), to_expr(start), to_expr(length)))


def flatten(col_) -> Column:
    from .. import collectionfns as C
    return Column(C.Flatten(to_expr(col_)))


def array_join(col_, delimiter: str, null_replacement=None) -> Column:
    from .. import collectionfns as C
    return Column(C.ArrayJoin(to_expr(col_), delimiter, null_replacement))


def array_union(a, b) -> Column:
    from .. import collectionfns as C
    return Column(C.ArrayUnion(to_expr(a), to_expr(b)))


def array_intersect(a, b) -> Column:
    from .. import collectionfns as C
    return Column(C.ArrayIntersect(to_expr(a), to_expr(b)))


def array_except(a, b) -> Column:
    from .. import collectionfns as C
    return Column(C.ArrayExcept(to_expr(a), to_expr(b)))


def get_json_object(col_, path: str) -> Column:
    from .. import collectionfns as C
    return Column(C.GetJsonObject(to_expr(col_), path))


def from_json(col_, schema) -> Column:
    from .. import collectionfns as C
    return Column(C.FromJson(to_expr(col_), schema))


def to_json(col_) -> Column:
    from .. import collectionfns as C
    return Column(C.ToJson(to_expr(col_)))


def lit(value: Any, dtype: Optional[T.DataType] = None) -> Column:
    return Column(E.Literal(value, dtype))


class _WhenBuilder(Column):
    def __init__(self, branches):
        self._branches = branches
        super().__init__(E.CaseWhen(branches, None))

    def when(self, cond, value) -> "_WhenBuilder":
        return _WhenBuilder(self._branches +
                            [(to_expr(cond), to_expr(value))])

    def otherwise(self, value) -> Column:
        return Column(E.CaseWhen(self._branches, to_expr(value)))


def when(cond, value) -> _WhenBuilder:
    return _WhenBuilder([(to_expr(cond), to_expr(value))])


def coalesce(*cols) -> Column:
    return Column(E.Coalesce(*[to_expr(c) for c in cols]))


def isnull(c) -> Column:
    return Column(E.IsNull(to_expr(c)))


def isnan(c) -> Column:
    return Column(E.IsNan(to_expr(c)))


def expr_abs(c) -> Column:
    return Column(E.Abs(to_expr(c)))


# -- aggregates -------------------------------------------------------------------

def sum(c) -> Column:  # noqa: A001 — mirrors pyspark naming
    return Column(A.Sum(to_expr(c)))


def count(c) -> Column:
    if isinstance(c, str) and c == "*":
        return Column(A.CountStar())
    return Column(A.Count(to_expr(c)))


def count_distinct(c, *more) -> Column:
    """count(DISTINCT cols): rewritten by the DataFrame layer into a
    dedup aggregation + count (Spark's two-phase distinct-aggregate
    lowering; joins back to the plain aggregates when mixed)."""
    cols = [to_expr(x) for x in (c,) + tuple(more)]
    return Column(_CountDistinctMarker(cols))


countDistinct = None  # assigned below (pyspark-compatible alias)


class _CountDistinctMarker(E.Expression):
    """Pseudo-aggregate consumed by DataFrame.agg/GroupedData.agg."""

    def __init__(self, cols):
        self.children = tuple(cols)
        from .. import types as T
        self.dtype = T.INT64
        self.nullable = False

    def _fp_extra(self):
        return "count_distinct"


class _GroupingMarker(E.Expression):
    """``grouping(col)`` (one child) or ``grouping_id()`` (none): consumed
    by the ``agg`` of a ``rollup`` / ``cube``, which rewrites it to bits of
    the ``spark_grouping_id`` column its Expand adds."""

    nullable = False

    def __init__(self, cols=()):
        self.children = tuple(cols)
        self.dtype = T.INT8 if self.children else T.INT64

    def references(self) -> set:
        return set()  # reads the grouping id, not the column it names

    def _fp_extra(self):
        return "grouping" if self.children else "grouping_id"


def grouping(c) -> Column:
    """1 where ``c`` is aggregated away in the row's grouping set (its NULL
    comes from the set, not the data), else 0; under rollup / cube only."""
    return Column(_GroupingMarker([to_expr(col(c) if isinstance(c, str)
                                           else c)]))


def grouping_id() -> Column:
    """The row's grouping set as Spark numbers it: bit ``n-1-i`` is set
    where the i-th of the n grouping columns is aggregated away."""
    return Column(_GroupingMarker())


def count_star() -> Column:
    return Column(A.CountStar())


def min(c) -> Column:  # noqa: A001
    return Column(A.Min(to_expr(c)))


def max(c) -> Column:  # noqa: A001
    return Column(A.Max(to_expr(c)))


def avg(c) -> Column:
    return Column(A.Average(to_expr(c)))


mean = avg


def first(c, ignore_nulls: bool = False) -> Column:
    return Column(A.First(to_expr(c), ignore_nulls))


def last(c, ignore_nulls: bool = False) -> Column:
    return Column(A.Last(to_expr(c), ignore_nulls))


# -- type parsing -----------------------------------------------------------------

_TYPE_NAMES = {
    "boolean": T.BOOLEAN, "bool": T.BOOLEAN,
    "tinyint": T.INT8, "byte": T.INT8,
    "smallint": T.INT16, "short": T.INT16,
    "int": T.INT32, "integer": T.INT32,
    "bigint": T.INT64, "long": T.INT64,
    "float": T.FLOAT32, "real": T.FLOAT32,
    "double": T.FLOAT64,
    "string": T.STRING,
    "date": T.DATE,
    "timestamp": T.TIMESTAMP,
}


# -- window functions ---------------------------------------------------------------

def row_number() -> Column:
    from ..windowfns import RowNumber
    return Column(RowNumber())


def rank() -> Column:
    from ..windowfns import Rank
    return Column(Rank())


def dense_rank() -> Column:
    from ..windowfns import DenseRank
    return Column(DenseRank())


def percent_rank() -> Column:
    from ..windowfns import PercentRank
    return Column(PercentRank())


def cume_dist() -> Column:
    from ..windowfns import CumeDist
    return Column(CumeDist())


def ntile(n: int) -> Column:
    from ..windowfns import NTile
    return Column(NTile(n))


def _colref(c) -> E.Expression:
    """str means a column NAME here (PySpark semantics for lag/lead)."""
    if isinstance(c, str):
        return E.UnresolvedColumn(c)
    return to_expr(c)


def lag(c, offset: int = 1, default=None) -> Column:
    from ..windowfns import Lag
    return Column(Lag(_colref(c), offset, default))


def lead(c, offset: int = 1, default=None) -> Column:
    from ..windowfns import Lead
    return Column(Lead(_colref(c), offset, default))


def parse_type(s: str) -> T.DataType:
    s = s.strip().lower()
    if s in _TYPE_NAMES:
        return _TYPE_NAMES[s]
    if s.startswith("decimal"):
        inner = s[s.index("(") + 1: s.index(")")]
        p, sc = (int(x) for x in inner.split(","))
        return T.decimal(p, sc)
    raise ValueError(f"unknown type name {s!r}")


# ------------------------------------------------------------------------------------
# Math functions (mathExpressions.scala analogs)
# ------------------------------------------------------------------------------------

def _mathmod():
    from .. import mathfns as M
    return M


def sqrt(c):
    return Column(_mathmod().Sqrt(_colref(c)))


def cbrt(c):
    return Column(_mathmod().Cbrt(_colref(c)))


def exp(c):
    return Column(_mathmod().Exp(_colref(c)))


def expm1(c):
    return Column(_mathmod().Expm1(_colref(c)))


def log(c):
    return Column(_mathmod().Log(_colref(c)))


def log10(c):
    return Column(_mathmod().Log10(_colref(c)))


def log2(c):
    return Column(_mathmod().Log2(_colref(c)))


def log1p(c):
    return Column(_mathmod().Log1p(_colref(c)))


def sin(c):
    return Column(_mathmod().Sin(_colref(c)))


def cos(c):
    return Column(_mathmod().Cos(_colref(c)))


def tan(c):
    return Column(_mathmod().Tan(_colref(c)))


def asin(c):
    return Column(_mathmod().Asin(_colref(c)))


def acos(c):
    return Column(_mathmod().Acos(_colref(c)))


def atan(c):
    return Column(_mathmod().Atan(_colref(c)))


def sinh(c):
    return Column(_mathmod().Sinh(_colref(c)))


def cosh(c):
    return Column(_mathmod().Cosh(_colref(c)))


def tanh(c):
    return Column(_mathmod().Tanh(_colref(c)))


def degrees(c):
    return Column(_mathmod().ToDegrees(_colref(c)))


def radians(c):
    return Column(_mathmod().ToRadians(_colref(c)))


def signum(c):
    return Column(_mathmod().Signum(_colref(c)))


def floor(c):
    return Column(_mathmod().Floor(_colref(c)))


def ceil(c):
    return Column(_mathmod().Ceil(_colref(c)))


def round(c, scale: int = 0):  # noqa: A001
    return Column(_mathmod().Round(_colref(c), scale))


def bround(c, scale: int = 0):
    return Column(_mathmod().BRound(_colref(c), scale))


def pow(l, r):  # noqa: A001
    return Column(_mathmod().Pow(_colref(l), _colref(r)))


def atan2(l, r):
    return Column(_mathmod().Atan2(_colref(l), _colref(r)))


def hypot(l, r):
    return Column(_mathmod().Hypot(_colref(l), _colref(r)))


def greatest(*cols):
    return Column(_mathmod().Greatest(*[_colref(c) for c in cols]))


def least(*cols):
    return Column(_mathmod().Least(*[_colref(c) for c in cols]))


# ------------------------------------------------------------------------------------
# Datetime functions (datetimeExpressions.scala analogs)
# ------------------------------------------------------------------------------------

def _dtmod():
    from .. import datetimefns as D
    return D


def year(c):
    return Column(_dtmod().Year(_colref(c)))


def month(c):
    return Column(_dtmod().Month(_colref(c)))


def dayofmonth(c):
    return Column(_dtmod().DayOfMonth(_colref(c)))


def quarter(c):
    return Column(_dtmod().Quarter(_colref(c)))


def dayofweek(c):
    return Column(_dtmod().DayOfWeek(_colref(c)))


def weekday(c):
    return Column(_dtmod().WeekDay(_colref(c)))


def dayofyear(c):
    return Column(_dtmod().DayOfYear(_colref(c)))


def weekofyear(c):
    return Column(_dtmod().WeekOfYear(_colref(c)))


def last_day(c):
    return Column(_dtmod().LastDay(_colref(c)))


def date_add(c, days):
    return Column(_dtmod().DateAdd(_colref(c), _colref(days)))


def date_sub(c, days):
    return Column(_dtmod().DateSub(_colref(c), _colref(days)))


def datediff(end, start):
    return Column(_dtmod().DateDiff(_colref(end), _colref(start)))


def add_months(c, months):
    return Column(_dtmod().AddMonths(_colref(c), _colref(months)))


def months_between(end, start):
    return Column(_dtmod().MonthsBetween(_colref(end), _colref(start)))


def trunc(c, fmt: str):
    return Column(_dtmod().TruncDate(_colref(c), fmt))


# ------------------------------------------------------------------------------------
# String functions (stringFunctions.scala analogs; CPU-evaluated — see
# stringfns.py module docstring)
# ------------------------------------------------------------------------------------

def _strmod():
    from .. import stringfns as S
    return S


def _val(v) -> E.Expression:
    """Literal coercion for args that are plain VALUES in the pyspark
    signature (lpad/rpad pad, locate substr, substring_index delim/count,
    like patterns) — unlike ColumnOrName args, a str here is data."""
    return to_expr(v)


def length(c):
    return Column(_strmod().Length(_colref(c)))


def upper(c):
    return Column(_strmod().Upper(_colref(c)))


def lower(c):
    return Column(_strmod().Lower(_colref(c)))


def reverse(c):
    return Column(_strmod().Reverse(_colref(c)))


def initcap(c):
    return Column(_strmod().InitCap(_colref(c)))


def trim(c):
    return Column(_strmod().StringTrim(_colref(c)))


def ltrim(c):
    return Column(_strmod().StringTrimLeft(_colref(c)))


def rtrim(c):
    return Column(_strmod().StringTrimRight(_colref(c)))


def substring(c, pos, length):  # noqa: A002
    return Column(_strmod().Substring(
        _colref(c), _colref(pos), _colref(length)))


def concat(*cols):
    return Column(_strmod().Concat(*[_colref(c) for c in cols]))


def concat_ws(sep: str, *cols):
    return Column(_strmod().ConcatWs(sep, *[_colref(c) for c in cols]))


def startswith(c, prefix):
    return Column(_strmod().StartsWith(_colref(c), _colref(prefix)))


def endswith(c, suffix):
    return Column(_strmod().EndsWith(_colref(c), _colref(suffix)))


def contains(c, needle):
    return Column(_strmod().Contains(_colref(c), _colref(needle)))


def like(c, pattern: str, escape: str = "\\"):
    return Column(_strmod().Like(_colref(c), pattern, escape))


def rlike(c, pattern: str):
    return Column(_strmod().RLike(_colref(c), pattern))


def regexp_extract(c, pattern: str, idx: int = 1):
    return Column(_strmod().RegExpExtract(_colref(c), pattern, idx))


def regexp_replace(c, pattern: str, replacement: str):
    return Column(_strmod().RegExpReplace(_colref(c), pattern, replacement))


def replace(c, search, replacement):
    return Column(_strmod().StringReplace(
        _colref(c), _colref(search), _colref(replacement)))


def lpad(c, length, pad):  # noqa: A002
    return Column(_strmod().StringLpad(
        _colref(c), _colref(length), _val(pad)))


def rpad(c, length, pad):  # noqa: A002
    return Column(_strmod().StringRpad(
        _colref(c), _colref(length), _val(pad)))


def repeat(c, n):
    return Column(_strmod().StringRepeat(_colref(c), _colref(n)))


def locate(substr, c, pos=1):
    return Column(_strmod().StringLocate(
        _val(substr), _colref(c), _val(pos)))


def instr(c, substr):
    return Column(_strmod().StringLocate(
        _val(substr), _colref(c), _val(1)))


def substring_index(c, delim, count):
    return Column(_strmod().SubstringIndex(
        _colref(c), _val(delim), _val(count)))


# ------------------------------------------------------------------------------------
# Statistical aggregates (AggregateFunctions.scala analogs)
# ------------------------------------------------------------------------------------

def stddev(c) -> Column:
    return Column(A.StddevSamp(to_expr(_colref(c))))


stddev_samp = stddev


def stddev_pop(c) -> Column:
    return Column(A.StddevPop(to_expr(_colref(c))))


def variance(c) -> Column:
    return Column(A.VarianceSamp(to_expr(_colref(c))))


var_samp = variance


def var_pop(c) -> Column:
    return Column(A.VariancePop(to_expr(_colref(c))))


def corr(x, y) -> Column:
    return Column(A.Corr(_colref(x), _colref(y)))


def covar_pop(x, y) -> Column:
    return Column(A.CovarPop(_colref(x), _colref(y)))


def covar_samp(x, y) -> Column:
    return Column(A.CovarSamp(_colref(x), _colref(y)))


def percentile(c, q: float) -> Column:
    return Column(A.Percentile(_colref(c), q))


def percentile_approx(c, q: float, accuracy: int = 10000) -> Column:
    """Spark-contract approximate percentile. Defaults to the EXACT
    percentile (rank error 0 <= n/accuracy, trivially satisfying the
    contract; CPU-operator path). For a device-resident mergeable
    estimator that flows through the two-phase exchange, use
    ``moments_percentile`` (distributional accuracy, no rank bound)."""
    return Column(A.Percentile(_colref(c), q))


approx_percentile = percentile_approx


def moments_percentile(c, q: float) -> Column:
    """Device moments-sketch percentile estimate (aggfns.ApproxPercentile:
    n, sum(x..x^4), min, max buffers — sum/min/max reducible, so the
    sketch merges through the exchange like the reference's t-digest).
    Accuracy is distributional (good on smooth data), NOT rank-bounded —
    prefer percentile_approx when the Spark contract matters."""
    return Column(A.ApproxPercentile(_colref(c), q))


# -- user-defined functions (RapidsUDF / GpuUserDefinedFunction analogs) ----------
def udf(fn=None, *, return_type=None, name=None):
    """Python UDF — the enclosing operator falls back to CPU (the planner
    tags it with an explain reason), matching the reference's treatment of
    opaque Scala UDFs."""
    from ..udf import udf as _udf
    kwargs = {}
    if return_type is not None:
        kwargs["return_type"] = return_type
    if name is not None:
        kwargs["name"] = name
    return _udf(fn, **kwargs) if fn is not None else _udf(**kwargs)


def tpu_udf(fn=None, *, return_type=None, name=None):
    """Device UDF (RapidsUDF analog): fn is jax-traceable over jnp arrays
    and fuses into the stage's XLA computation."""
    from ..udf import tpu_udf as _tpu_udf
    kwargs = {}
    if return_type is not None:
        kwargs["return_type"] = return_type
    if name is not None:
        kwargs["name"] = name
    return _tpu_udf(fn, **kwargs) if fn is not None else _tpu_udf(**kwargs)


def collect_list(c) -> Column:
    """Group values into an array (runs on the CPU operator; result rides
    as a host arrow list column)."""
    return Column(A.CollectList(_colref(c)))


def collect_set(c) -> Column:
    return Column(A.CollectSet(_colref(c)))


def pandas_udf(fn=None, *, return_type=None, name=None):
    """Vectorized pandas UDF (Series -> Series) on the CPU operator."""
    from ..udf import pandas_udf as _pudf
    kwargs = {}
    if return_type is not None:
        kwargs["return_type"] = return_type
    if name is not None:
        kwargs["name"] = name
    return _pudf(fn, **kwargs) if fn is not None else _pudf(**kwargs)


# -- bitwise / hash ---------------------------------------------------------------

def bitwise_not(c) -> Column:
    from .. import bitwisefns as B
    return Column(B.BitwiseNot(_colref(c)))


bitwiseNOT = bitwise_not  # pyspark alias


def shiftleft(c, n) -> Column:
    from .. import bitwisefns as B
    return Column(B.ShiftLeft(_colref(c), to_expr(n)))


def shiftright(c, n) -> Column:
    from .. import bitwisefns as B
    return Column(B.ShiftRight(_colref(c), to_expr(n)))


def shiftrightunsigned(c, n) -> Column:
    from .. import bitwisefns as B
    return Column(B.ShiftRightUnsigned(_colref(c), to_expr(n)))


def hash(*cols) -> Column:  # noqa: A001 — mirrors pyspark naming
    """Spark-exact murmur3 row hash, seed 42 (GpuMurmur3Hash)."""
    from .. import bitwisefns as B
    return Column(B.Murmur3Hash(*[_colref(c) for c in cols]))


def interleave_bits(*cols) -> Column:
    """Z-order (Morton) index of integer columns — the clustering key
    OPTIMIZE ZORDER BY sorts by (zorder/ZOrderRules.scala
    GpuInterleaveBits analog; used by io.delta.delta_zorder)."""
    from .. import bitwisefns as B
    return Column(B.InterleaveBits(*[_colref(c) for c in cols]))


def xxhash64(*cols) -> Column:
    """Spark-exact xxhash64 row hash, seed 42 (GpuXxHash64)."""
    from .. import bitwisefns as B
    return Column(B.XxHash64(*[_colref(c) for c in cols]))


countDistinct = count_distinct  # pyspark alias

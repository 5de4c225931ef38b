"""count(DISTINCT) beside plain aggregates, lowered to two stacked aggregates
over ONE copy of the child (``sql/dataframe._plan_distinct_one_pass``).

Differential contract: the one-pass form answers what the join form
(``_plan_count_distinct_join``, kept as the fallback and called directly
here) answers and what pandas answers, under the join form's schema; the
child stands once in its plan; what does not re-aggregate keeps the join
form; ``QueryStats.distinct_one_pass_aggs`` says which ran.
"""

import decimal

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.sql import dataframe as D
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.utils.metrics import QueryStats

N = 600
REL = 1e-12


def _table(n=N):
    rng = np.random.default_rng(36)
    dec = [decimal.Decimal(int(x)) / 100
           for x in rng.integers(-99999, 99999, n)]
    return pa.table({
        "k": pa.array(rng.integers(0, 5, n)),
        # distinct columns: an int with NULLs, one that is all NULL, a
        # string with NULLs, a second int
        "v": pa.array([None if i % 7 == 0 else int(x) for i, x in
                       enumerate(rng.integers(0, 30, n))], type=pa.int64()),
        "n": pa.array([None] * n, type=pa.int64()),
        "s": pa.array([None if i % 5 == 0 else f"s{x}" for i, x in
                       enumerate(rng.integers(0, 13, n))]),
        "v2": pa.array(rng.integers(0, 3, n)),
        # what the plain aggregates read
        "i": pa.array(rng.integers(-50, 50, n).astype(np.int32)),
        "w": pa.array(rng.uniform(-1.0, 1.0, n)),
        "x": pa.array([None if i % 11 == 0 else float(x) for i, x in
                       enumerate(rng.uniform(1.0, 2.0, n))]),
        "d": pa.array([None if i % 13 == 0 else v
                       for i, v in enumerate(dec)],
                      type=pa.decimal128(7, 2)),
    })


def _sum(col):
    def of(g):
        vals = [v for v in g[col] if v is not None and v == v]
        return sum(vals[1:], vals[0]) if vals else None
    return of


def _avg(col):
    def of(g):
        vals = [float(v) for v in g[col] if v is not None and v == v]
        return sum(vals) / len(vals) if vals else None
    return of


def _avg_decimal(col):
    """Spark's avg of a decimal(7,2): the sum over the count at Divide's
    decimal(38,23), then the result's decimal(11,6), HALF_UP each time."""
    ctx = decimal.Context(prec=60, rounding=decimal.ROUND_HALF_UP)

    def of(g):
        vals = [v for v in g[col] if v is not None]
        if not vals:
            return None
        q = ctx.divide(sum(vals, decimal.Decimal(0)), len(vals))
        return q.quantize(decimal.Decimal("1E-23"), context=ctx).quantize(
            decimal.Decimal("1E-6"), context=ctx)
    return of


def _least(col, fn):
    def of(g):
        vals = [v for v in g[col] if v is not None and v == v]
        return fn(vals) if vals else None
    return of


def _ratio(a, b):
    def of(g):
        num, den = _sum(a)(g), _sum(b)(g)
        return None if num is None or not den else num / den
    return of


# name -> [(output name, Column, oracle over one group's rows)]
PLAIN = {
    "none": [],
    "sum_int": [("a", F.sum(F.col("i")), _sum("i"))],
    "sum_float": [("a", F.sum(F.col("w")), _sum("w"))],
    "sum_float_nulls": [("a", F.sum(F.col("x")), _sum("x"))],
    "count": [("a", F.count(F.col("x")),
               lambda g: sum(v is not None and v == v for v in g["x"]))],
    "count_star": [("a", F.count_star(), len)],
    "min_max": [("a", F.min(F.col("w")), _least("w", min)),
                ("b", F.max(F.col("i")), _least("i", max)),
                ("c", F.min(F.col("d")), _least("d", min))],
    "avg": [("a", F.avg(F.col("x")), _avg("x")),
            ("b", F.avg(F.col("i")), _avg("i"))],
    "compound": [("a", F.sum(F.col("w")) / F.sum(F.col("i")),
                  _ratio("w", "i"))],
    "same_leaf_twice": [("a", F.sum(F.col("w")), _sum("w")),
                        ("b", F.sum(F.col("w")) * F.lit(2.0),
                         lambda g: _sum("w")(g) * 2.0 if len(g) else None),
                        ("c", F.sum(F.col("w")), _sum("w"))],
    "q95": [("a", F.sum(F.col("x")), _sum("x")),
            ("b", F.sum(F.col("w")), _sum("w"))],
}
# name -> the distinct set's columns
DISTINCT = {
    "int_with_nulls": ["v"],
    "all_null": ["n"],
    "string": ["s"],
    "two_columns": ["v", "v2"],
}
# the written order: the count first, last, or between the plain ones
CASES = ([(p, "int_with_nulls", 0) for p in PLAIN]
         + [("q95", d, 1) for d in DISTINCT if d != "int_with_nulls"]
         + [("min_max", "two_columns", 99), ("avg", "string", 99)])


def _columns(plain, distinct, at):
    cols = [c.alias(n) for n, c, _ in PLAIN[plain]]
    cd = F.count_distinct(*[F.col(c) for c in DISTINCT[distinct]])
    at = min(at, len(cols))
    return cols[:at] + [cd.alias("cd")] + cols[at:]


def _both_forms(df, keys, cols):
    """(one-pass form, join form) of ``df.group_by(keys).agg(cols)``: the
    two builders called as ``GroupedData.agg`` calls them."""
    agg_exprs = [D._named(c) for c in cols]
    group_exprs = [D._named(k) for k in keys]
    cds, plain = D._split_count_distinct(agg_exprs)
    order = [n for n, _ in agg_exprs]
    one = D._plan_distinct_one_pass(df._plan, group_exprs, cds[0], plain,
                                    order)
    assert one is not None
    return (D.DataFrame(one, df.session),
            D._plan_count_distinct_join(df, group_exprs, cds, plain, order))


def _oracle(table, keys, plain, distinct, at):
    pdf = table.to_pandas().astype(object)
    pdf = pdf.where(pdf.notna(), None)
    groups = ([(k, g) for k, g in pdf.groupby(keys[0])] if keys
              else [(None, pdf)])
    dcols = DISTINCT[distinct]
    rows = []
    for k, g in groups:
        seen = {tuple(r) for r in g[dcols].itertuples(index=False)
                if all(v is not None for v in r)}
        vals = [fn(g) for _, _, fn in PLAIN[plain]]
        at_ = min(at, len(vals))
        vals = vals[:at_] + [len(seen)] + vals[at_:]
        rows.append(tuple(([int(k)] if keys else []) + vals))
    return rows


def _close(a, b):
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, float) or isinstance(b, float):
        return abs(float(a) - float(b)) <= REL * max(1.0, abs(float(b)))
    return a == b


def _assert_same(got, want):
    def key(r):
        return tuple((x is None, str(x)) for x in r[:1])
    got, want = sorted(got, key=key), sorted(want, key=key)
    assert len(got) == len(want), (got, want)
    for g, w in zip(got, want):
        assert len(g) == len(w) and all(map(_close, g, w)), (g, w)


def _fields(df):
    return [(f.name, str(f.dtype), f.nullable) for f in df.schema.fields]


@pytest.fixture(scope="module")
def table():
    return _table()


@pytest.mark.parametrize("keys", [["k"], []], ids=["grouped", "groupless"])
@pytest.mark.parametrize("plain,distinct,at", CASES,
                         ids=[f"{p}-{d}-{a}" for p, d, a in CASES])
def test_one_pass_equals_the_join_form_and_pandas(session, table, keys,
                                                  plain, distinct, at):
    df = session.create_dataframe(table)
    cols = _columns(plain, distinct, at)
    one, join = _both_forms(df, keys, cols)
    assert _fields(one) == _fields(join)
    got = one.collect()
    _assert_same(got, join.collect())
    _assert_same(got, _oracle(table, keys, plain, distinct, at))
    # and that is the form agg() chooses
    assert "distinct_one_pass" in df.group_by(*keys).agg(
        *cols).explain_string()


@pytest.mark.parametrize("keys", [["k"], []], ids=["grouped", "groupless"])
@pytest.mark.parametrize("how", ["empty_input", "filter_selects_nothing"])
def test_no_rows(session, table, keys, how):
    """A groupless aggregate over no rows answers ONE row, (0, NULL, NULL)
    and a 0 for every count; a grouped one answers none."""
    df = (session.create_dataframe(table.slice(0, 0)) if how == "empty_input"
          else session.create_dataframe(table).filter(F.col("k") < 0))
    cols = [F.count_distinct(F.col("v")).alias("cd"),
            F.sum(F.col("x")).alias("a"), F.sum(F.col("i")).alias("b"),
            F.count(F.col("x")).alias("c"), F.count_star().alias("n"),
            F.min(F.col("w")).alias("lo"), F.avg(F.col("i")).alias("m")]
    one, join = _both_forms(df, keys, cols)
    assert _fields(one) == _fields(join)
    got = one.collect()
    assert got == join.collect()
    assert got == ([] if keys else [(0, None, None, 0, 0, None, None)])


def test_a_null_distinct_value_is_not_counted_and_its_row_is_summed(session):
    """A row whose distinct column is NULL is a level-1 group of its own:
    out of the count, in the sums."""
    t = pa.table({"k": [1, 1, 1, 2, 2],
                  "o": pa.array([7, None, 7, None, None], type=pa.int64()),
                  "c": [1.0, 2.0, 4.0, 8.0, 16.0]})
    df = session.create_dataframe(t)
    cols = [F.count_distinct(F.col("o")).alias("cd"),
            F.sum(F.col("c")).alias("s"), F.count_star().alias("n")]
    assert sorted(df.group_by("k").agg(*cols).collect()) == [
        (1, 1, 7.0, 3), (2, 0, 24.0, 2)]
    assert df.agg(*cols).collect() == [(1, 31.0, 5)]


def test_a_null_group_key_keeps_its_group(session):
    """The keys are grouped twice and never joined: a NULL key is a group
    like any other (the join form's inner join on the keys drops it)."""
    t = pa.table({"k": pa.array([1, None, None, 1], type=pa.int64()),
                  "v": [5, 5, 6, 5], "c": [1.0, 2.0, 4.0, 8.0]})
    df = session.create_dataframe(t)
    got = df.group_by("k").agg(F.count_distinct(F.col("v")).alias("cd"),
                               F.sum(F.col("c")).alias("s")).collect()
    assert sorted(got, key=str) == sorted([(1, 1, 9.0), (None, 2, 6.0)],
                                          key=str)


def test_the_child_stands_once_and_no_join_is_added(fresh_session, table):
    sess = fresh_session
    df = sess.create_dataframe(table).filter(F.col("i") > -40)
    cols = _columns("q95", "int_with_nulls", 0)
    for keys in (["k"], []):
        one, join = _both_forms(df, keys, cols)
        tree = sess._plan_physical(one._plan).tree_string()
        assert tree.count("TpuScan") == 1 and "Join" not in tree
        assert tree.count("TpuHashAggregate") == 2
        old = sess._plan_physical(join._plan).tree_string()
        assert old.count("TpuScan") == 2 and "Join" in old


FALLBACKS = {
    "two_distinct_sets": (
        [F.count_distinct(F.col("v")).alias("a"),
         F.sum(F.col("w")).alias("b"),
         F.count_distinct(F.col("s")).alias("c")],
        lambda g: (g.v.nunique(), g.w.sum(), g.s.nunique())),
    "first": (
        [F.count_distinct(F.col("v")).alias("a"),
         F.first(F.col("v2")).alias("b")],
        lambda g: (g.v.nunique(), g.v2.iloc[0])),
    "stddev": (
        [F.count_distinct(F.col("v")).alias("a"),
         F.stddev(F.col("w")).alias("b")],
        lambda g: (g.v.nunique(), g.w.std())),
    # the sum of sums would be a decimal past 18 digits, finalized on the
    # host: no device cast brings it back to decimal(17,2)
    "sum_decimal": (
        [F.count_distinct(F.col("v")).alias("a"),
         F.sum(F.col("d")).alias("b"), F.min(F.col("d")).alias("c")],
        lambda g: (g.v.nunique(), sum(v for v in g.d if v is not None),
                   min(v for v in g.d if v is not None))),
    # the average of a decimal is a decimal (Spark's Average), made on the
    # host from the sum's lanes: no merge of level-1 partials keeps it
    "avg_decimal": (
        [F.count_distinct(F.col("v")).alias("a"),
         F.avg(F.col("d")).alias("b")],
        lambda g: (g.v.nunique(), _avg_decimal("d")(g))),
    "sum_beside_stddev": (
        [F.sum(F.col("w")).alias("a"),
         F.count_distinct(F.col("v")).alias("b"),
         (F.stddev(F.col("w")) + F.sum(F.col("i"))).alias("c")],
        lambda g: (g.w.sum(), g.v.nunique(), g.w.std() + g.i.sum())),
}


@pytest.mark.parametrize("keys", [["k"], []], ids=["grouped", "groupless"])
@pytest.mark.parametrize("case", FALLBACKS)
def test_what_does_not_reaggregate_keeps_the_join_form(fresh_session, table,
                                                       keys, case):
    """Several distinct sets, or a plain aggregate with no exact merge by
    expression: the join form, chosen from the aggregate list alone."""
    cols, oracle = FALLBACKS[case]
    if case == "first":
        # first() of a constant: any order of the rows answers the same
        table = table.set_column(table.schema.get_field_index("v2"), "v2",
                                 pa.array([4] * len(table)))
    df = fresh_session.create_dataframe(table)
    q = df.group_by(*keys).agg(*cols)
    assert "distinct_one_pass" not in q.explain_string()
    with QueryStats.scoped() as qs:
        got = q.collect()
    assert qs.distinct_one_pass_aggs == 0
    pdf = table.to_pandas()
    want = ([(int(k),) + tuple(oracle(g)) for k, g in pdf.groupby("k")]
            if keys else [tuple(oracle(pdf))])
    assert len(got) == len(want)
    for g, w in zip(sorted(got), sorted(want)):
        assert all(abs(float(a) - float(b)) <= 1e-9 * max(1.0, abs(float(b)))
                   for a, b in zip(g, w)), (g, w)


@pytest.mark.parametrize("keys", [["k"], []], ids=["grouped", "groupless"])
def test_the_counter_and_the_mark(fresh_session, table, keys):
    """One a converted plan's level-2 aggregate; ``explain_string()`` shows
    the mark on that aggregate and not on level 1."""
    df = fresh_session.create_dataframe(table)
    q = df.group_by(*keys).agg(*_columns("q95", "int_with_nulls", 0))
    marked = [ln for ln in q.explain_string().splitlines()
              if "Aggregate keys=" in ln]
    assert len(marked) == 2
    assert marked[0].endswith("distinct_one_pass")
    assert "distinct_one_pass" not in marked[1] and "__cd0_0" in marked[1]
    before = QueryStats.process().distinct_one_pass_aggs
    with QueryStats.scoped() as qs:
        q.collect()
    assert qs.distinct_one_pass_aggs == 1
    assert QueryStats.process().distinct_one_pass_aggs == before + 1
    with QueryStats.scoped() as qs:
        df.group_by(*keys).agg(F.sum(F.col("w")).alias("a")).collect()
    assert qs.distinct_one_pass_aggs == 0


@pytest.mark.parametrize("keys", [["k"], []], ids=["grouped", "groupless"])
def test_on_the_mesh_it_answers_what_one_process_answers(fresh_session,
                                                         table, keys):
    """``shuffle.mode=ICI``: two stacked aggregates are two two-phase
    aggregates with their exchanges, on the 8-device CPU mesh."""
    sess = fresh_session
    df = sess.create_dataframe(table)
    q = df.group_by(*keys).agg(*_columns("avg", "int_with_nulls", 1),
                               F.sum(F.col("w")).alias("sw"),
                               F.count_star().alias("rows"))
    want = q.collect()
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "ICI")
    try:
        with QueryStats.scoped() as qs:
            got = q.collect()
    finally:
        sess.conf.set("spark.rapids.tpu.shuffle.mode", "CACHE_ONLY")
    assert qs.distinct_one_pass_aggs == 1
    _assert_same(got, want)

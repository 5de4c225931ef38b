"""Window function tests (window_function_test.py analog).

Differential: engine window results vs a transparent O(n^2) python oracle
that applies Spark frame semantics literally (peers, null skipping).
"""

import numpy as np
import pandas as pd
import pytest

from .support import DoubleGen, IntGen, assert_rows_equal, gen_table, pdf_rows


def F():
    from spark_rapids_tpu.sql import functions
    return functions


def W():
    from spark_rapids_tpu.sql.window import Window
    return Window


@pytest.fixture(scope="module")
def wdf(session, rng):
    table, pdf = gen_table(rng, {
        "p": IntGen(lo=0, hi=5, nullable=False),
        "o": IntGen(lo=0, hi=20),
        "u": IntGen(lo=0, hi=10**6, nullable=False),  # unique-ish tiebreak
        "v": IntGen(lo=-50, hi=50),
        "d": DoubleGen(special=False, nullable=False),
    }, 240)
    # make u truly unique so ROWS frames are deterministic
    pdf = pdf.copy()
    pdf["u"] = np.arange(len(pdf), dtype=np.int64)
    import pyarrow as pa
    table = table.set_column(table.schema.get_field_index("u"), "u",
                             pa.array(pdf["u"].to_numpy()))
    return session.create_dataframe(table), pdf


# ------------------------------------------------------------------------------------
# Oracle
# ------------------------------------------------------------------------------------

def _null(x):
    return x is None or x is pd.NA or (isinstance(x, float) and np.isnan(x))


def oracle(pdf, parts, orders, func, frame=("rows", None, None), arg=None):
    """Window value per original row; Spark semantics, brute force."""
    rows = pdf_rows(pdf)
    cols = list(pdf.columns)

    def cell(r, c):
        return rows[r][cols.index(c)]

    n = len(rows)
    # partition groups
    groups = {}
    for i in range(n):
        key = tuple((cell(i, c) is None, cell(i, c)) for c in parts)
        groups.setdefault(key, []).append(i)
    out = [None] * n
    kind, lo, hi = frame
    for key, idxs in groups.items():
        # sort within partition by order cols asc nulls-first, stable
        def okey(i):
            return tuple((not _null(cell(i, c)),
                          cell(i, c) if not _null(cell(i, c)) else 0)
                         for c in orders)
        idxs = sorted(idxs, key=okey)
        m = len(idxs)
        okeys = [okey(i) for i in idxs]
        for pos, i in enumerate(idxs):
            if func == "row_number":
                out[i] = pos + 1
                continue
            if func == "rank":
                out[i] = okeys.index(okeys[pos]) + 1
                continue
            if func == "dense_rank":
                seen = []
                for k in okeys[: pos + 1]:
                    if not seen or seen[-1] != k:
                        seen.append(k)
                out[i] = len(seen)
                continue
            if func == "lag":
                src = pos - arg[0]
                out[i] = (cell(idxs[src], arg[1])
                          if 0 <= src < m else arg[2])
                continue
            if func == "lead":
                src = pos + arg[0]
                out[i] = (cell(idxs[src], arg[1])
                          if 0 <= src < m else arg[2])
                continue
            # framed aggregate over column arg
            if kind == "rows":
                a = 0 if lo is None else max(0, pos + lo)
                b = m - 1 if hi is None else min(m - 1, pos + hi)
            else:  # range
                if lo is None and hi is None:
                    a, b = 0, m - 1
                else:  # unbounded preceding .. current peer group end
                    a = 0
                    b = pos
                    while b + 1 < m and okeys[b + 1] == okeys[pos]:
                        b += 1
            if func == "count(*)":
                out[i] = max(0, b - a + 1)
                continue
            vals = [cell(idxs[j], arg) for j in range(a, b + 1)
                    if a <= b and not _null(cell(idxs[j], arg))]
            if func == "count":
                out[i] = len(vals)
            elif not vals:
                out[i] = None
            elif func == "sum":
                out[i] = sum(vals)
            elif func == "min":
                out[i] = min(vals)
            elif func == "max":
                out[i] = max(vals)
            elif func == "avg":
                out[i] = float(sum(vals)) / len(vals)
            else:
                raise ValueError(func)
    return out


def run_and_compare(df, pdf, wcol, parts, orders, func,
                    frame=("rows", None, None), arg=None, approx=False):
    got = df.select(*pdf.columns, wcol.alias("wout")).collect()
    exp_w = oracle(pdf, parts, orders, func, frame, arg)
    exp = [r + (exp_w[i],) for i, r in enumerate(pdf_rows(pdf))]
    assert_rows_equal(got, exp, approx_float=approx)


# ------------------------------------------------------------------------------------
# Ranking family
# ------------------------------------------------------------------------------------

def test_row_number(wdf):
    df, pdf = wdf
    f, w = F(), W()
    spec = w.partition_by("p").order_by("u")
    run_and_compare(df, pdf, f.row_number().over(spec), ["p"], ["u"],
                    "row_number")


def test_rank_dense_rank_with_ties(wdf):
    df, pdf = wdf
    f, w = F(), W()
    spec = w.partition_by("p").order_by("o")  # o has ties and nulls
    run_and_compare(df, pdf, f.rank().over(spec), ["p"], ["o"], "rank")
    run_and_compare(df, pdf, f.dense_rank().over(spec), ["p"], ["o"],
                    "dense_rank")


def test_ntile_and_percent_rank(wdf):
    df, pdf = wdf
    f, w = F(), W()
    spec = w.partition_by("p").order_by("u")
    got = df.select("p", "u",
                    f.ntile(4).over(spec).alias("nt"),
                    f.percent_rank().over(spec).alias("pr"),
                    f.cume_dist().over(spec).alias("cd")).to_pandas()
    exp = pdf[["p", "u"]].copy()
    g = pdf.sort_values(["p", "u"]).groupby("p")["u"]
    for p, grp in pdf.groupby("p"):
        sz = len(grp)
        order = grp.sort_values("u").index
        for pos, idx in enumerate(order):
            base, rem = sz // 4, sz % 4
            nt = (pos // (base + 1) if pos < (base + 1) * rem
                  else rem + (pos - (base + 1) * rem) // max(base, 1)) + 1
            exp.loc[idx, "nt"] = nt
            exp.loc[idx, "pr"] = pos / (sz - 1) if sz > 1 else 0.0
            exp.loc[idx, "cd"] = (pos + 1) / sz
    merged = got.merge(exp, on=["p", "u"], suffixes=("", "_e"))
    assert len(merged) == len(pdf)
    assert (merged["nt"] == merged["nt_e"]).all()
    assert np.allclose(merged["pr"], merged["pr_e"])
    assert np.allclose(merged["cd"], merged["cd_e"])


# ------------------------------------------------------------------------------------
# lag / lead
# ------------------------------------------------------------------------------------

def test_lag_lead(wdf):
    df, pdf = wdf
    f, w = F(), W()
    spec = w.partition_by("p").order_by("u")
    run_and_compare(df, pdf, f.lag("v", 1).over(spec), ["p"], ["u"],
                    "lag", arg=(1, "v", None))
    run_and_compare(df, pdf, f.lead("v", 2).over(spec), ["p"], ["u"],
                    "lead", arg=(2, "v", None))


def test_lag_with_default(wdf):
    df, pdf = wdf
    f, w = F(), W()
    spec = w.partition_by("p").order_by("u")
    run_and_compare(df, pdf, f.lag("u", 3, -1).over(spec), ["p"], ["u"],
                    "lag", arg=(3, "u", -1))


# ------------------------------------------------------------------------------------
# Framed aggregates
# ------------------------------------------------------------------------------------

def test_running_sum_rows(wdf):
    df, pdf = wdf
    f, w = F(), W()
    spec = w.partition_by("p").order_by("u").rows_between(
        w.unboundedPreceding, w.currentRow)
    run_and_compare(df, pdf, f.sum(f.col("v")).over(spec), ["p"], ["u"],
                    "sum", ("rows", None, 0), "v")


def test_default_range_frame_ties(wdf):
    """ORDER BY with no explicit frame = RANGE UNBOUNDED..CURRENT (peers
    share the value)."""
    df, pdf = wdf
    f, w = F(), W()
    spec = w.partition_by("p").order_by("o")
    run_and_compare(df, pdf, f.sum(f.col("v")).over(spec), ["p"], ["o"],
                    "sum", ("range", None, 0), "v")
    run_and_compare(df, pdf, f.count(f.col("v")).over(spec), ["p"], ["o"],
                    "count", ("range", None, 0), "v")


def test_whole_partition_agg(wdf):
    df, pdf = wdf
    f, w = F(), W()
    spec = w.partition_by("p")
    run_and_compare(df, pdf, f.sum(f.col("v")).over(spec), ["p"], [],
                    "sum", ("rows", None, None), "v")
    run_and_compare(df, pdf, f.max(f.col("v")).over(spec), ["p"], [],
                    "max", ("rows", None, None), "v")
    run_and_compare(df, pdf, f.avg(f.col("d")).over(spec), ["p"], [],
                    "avg", ("rows", None, None), "d", approx=True)


def test_sliding_rows_frame(wdf):
    df, pdf = wdf
    f, w = F(), W()
    spec = w.partition_by("p").order_by("u").rows_between(-2, 2)
    run_and_compare(df, pdf, f.sum(f.col("v")).over(spec), ["p"], ["u"],
                    "sum", ("rows", -2, 2), "v")
    run_and_compare(df, pdf, f.count(f.col("v")).over(spec), ["p"], ["u"],
                    "count", ("rows", -2, 2), "v")


def test_running_min_max(wdf):
    df, pdf = wdf
    f, w = F(), W()
    spec = w.partition_by("p").order_by("u").rows_between(
        w.unboundedPreceding, 0)
    run_and_compare(df, pdf, f.min(f.col("v")).over(spec), ["p"], ["u"],
                    "min", ("rows", None, 0), "v")
    run_and_compare(df, pdf, f.max(f.col("v")).over(spec), ["p"], ["u"],
                    "max", ("rows", None, 0), "v")


def test_count_star_window(wdf):
    df, pdf = wdf
    f, w = F(), W()
    spec = w.partition_by("p").order_by("u").rows_between(
        w.unboundedPreceding, 0)
    run_and_compare(df, pdf, f.count_star().over(spec), ["p"], ["u"],
                    "count(*)", ("rows", None, 0), None)


def test_no_partition_window(wdf):
    """Empty PARTITION BY: one global partition."""
    df, pdf = wdf
    f, w = F(), W()
    spec = w.order_by("u")
    run_and_compare(df, pdf, f.row_number().over(spec), [], ["u"],
                    "row_number")


def test_multiple_windows_one_select(wdf):
    df, pdf = wdf
    f, w = F(), W()
    s1 = w.partition_by("p").order_by("u")
    got = df.select(
        "p", "u",
        f.row_number().over(s1).alias("rn"),
        f.sum(f.col("v")).over(s1.rows_between(w.unboundedPreceding, 0))
         .alias("rs"),
    ).collect()
    rn = oracle(pdf, ["p"], ["u"], "row_number")
    rs = oracle(pdf, ["p"], ["u"], "sum", ("rows", None, 0), "v")
    rows = pdf_rows(pdf[["p", "u"]])
    exp = [r + (rn[i], rs[i]) for i, r in enumerate(rows)]
    assert_rows_equal(got, exp)


def test_window_on_tpu_plan(wdf):
    """The window must actually plan on the device (no CPU fallback)."""
    df, _ = wdf
    f, w = F(), W()
    spec = w.partition_by("p").order_by("u")
    s = df.select("p", f.row_number().over(spec).alias("rn")).explain_string()
    assert "Window" in s
    assert "!" not in s.split("Window")[1].split("\n")[0]


def test_sliding_min_max_cpu_fallback(wdf):
    """Bounded sliding min/max is declined by the device and must be
    computed correctly by the CPU fallback."""
    df, pdf = wdf
    f, w = F(), W()
    spec = w.partition_by("p").order_by("u").rows_between(-1, 0)
    run_and_compare(df, pdf, f.min(f.col("v")).over(spec), ["p"], ["u"],
                    "min", ("rows", -1, 0), "v")
    run_and_compare(df, pdf, f.max(f.col("v")).over(spec), ["p"], ["u"],
                    "max", ("rows", -1, 0), "v")


def test_frame_survives_order_by():
    """An explicit frame set before order_by must be preserved (PySpark
    WindowSpec semantics)."""
    w = W()
    spec = w.partition_by("p").rows_between(-1, 0).order_by("u")
    assert spec._spec.frame.kind == "rows"
    assert (spec._spec.frame.lo, spec._spec.frame.hi) == (-1, 0)
    # and the implicit default still recomputes
    spec2 = w.partition_by("p").order_by("u")
    assert spec2._spec.frame.kind == "range"


def test_window_string_partition_falls_back(session):
    """String partition keys run on the device as dictionary codes (they
    fell back to the CPU until the reporting queries needed them): the
    same plan has no ``!`` and gives the same rows."""
    import pyarrow as pa
    f, w = F(), W()
    table = pa.table({
        "s": pa.array(["a", "b", "a", "c", "b", "a", None, "c"]),
        "x": pa.array([1, 2, 3, 4, 5, 6, 7, 8], type=pa.int64()),
    })
    df = session.create_dataframe(table)
    spec = w.partition_by("s").order_by("x")
    out = df.select("s", "x", f.row_number().over(spec).alias("rn"))
    plan = out.explain_string()
    assert "!" not in plan.split("\n", 2)[2]  # below the legend: all on TPU
    assert "TpuWindow" in session._plan_physical(out._plan).tree_string()
    got = out.collect()
    pdf = table.to_pandas()
    exp_rn = pdf.sort_values(["x"]).groupby("s", dropna=False).cumcount() + 1
    svals = table.column("s").to_pylist()
    exp = [(svals[i], int(pdf["x"][i]), int(exp_rn[i]))
           for i in range(len(pdf))]
    assert_rows_equal(got, exp)


def test_lag_with_column_default(wdf):
    """Column-valued lag default must be permuted into sorted output order
    (regression: defaults were taken in input row order)."""
    df, pdf = wdf
    f, w = F(), W()
    from spark_rapids_tpu.sql.column import Column as C
    from spark_rapids_tpu import exprs as E
    from spark_rapids_tpu.windowfns import Lag, WindowExpression
    spec = w.partition_by("p").order_by("u")
    wexpr = C(WindowExpression(
        Lag(E.UnresolvedColumn("v"), 1, E.UnresolvedColumn("u")),
        spec._spec))
    got = df.select("p", "u", "v", wexpr.alias("wout")).collect()
    sp = pdf.sort_values(["p", "u"]).reset_index()
    exp_map = {}
    for p in sp["p"].unique():
        g = sp[sp["p"] == p]
        prev_v = None
        for _, row in g.iterrows():
            if prev_v is None:
                exp_map[(row["p"], row["u"])] = row["u"]  # default = u
            else:
                exp_map[(row["p"], row["u"])] = prev_v
            prev_v = row["v"] if not pd.isna(row["v"]) else np.nan
    for p_, u_, v_, wout in got:
        exp = exp_map[(p_, u_)]
        if isinstance(exp, float) and np.isnan(exp):
            assert wout is None
        else:
            assert wout == exp, (p_, u_, wout, exp)


def test_window_survives_injected_oom(session):
    """Window op under injectRetryOOM=1 retries and still yields correct
    results (GpuWindowExec withRetryNoSplit analog)."""
    import pyarrow as pa
    f, w = F(), W()
    table = pa.table({
        "p": pa.array([0, 0, 1, 1, 0, 1], type=pa.int64()),
        "x": pa.array([3, 1, 5, 2, 6, 4], type=pa.int64()),
    })
    df = session.create_dataframe(table)
    session.conf.set("spark.rapids.tpu.test.injectRetryOOM", 1)
    try:
        spec = w.partition_by("p").order_by("x")
        got = df.select("p", "x", f.row_number().over(spec).alias("rn")) \
                .collect()
    finally:
        session.conf.set("spark.rapids.tpu.test.injectRetryOOM", 0)
    exp = {(0, 1): 1, (0, 3): 2, (0, 6): 3, (1, 2): 1, (1, 4): 2, (1, 5): 3}
    assert len(got) == 6
    for p_, x_, rn in got:
        assert rn == exp[(p_, x_)]

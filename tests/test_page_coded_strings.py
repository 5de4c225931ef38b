"""Low-cardinality string columns read as their parquet pages' codes.

Every test runs over two files of the same rows: one the writer
dictionary-coded (``coded``: the scan keeps such a column as
``PageCodedStringColumn``, the aggregate remaps its page dictionaries) and
one written with ``use_dictionary=False`` (``plain``: strings, hashed as
before).  Both must answer what pandas answers; the counter
``QueryStats.page_coded_keys`` says which path coded the keys.
"""

import math

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.batch import (HostStringColumn, PageCodedStringColumn,
                                    from_arrow)
from spark_rapids_tpu.io.parquet import ParquetSource, page_coded_columns
from spark_rapids_tpu.ops.strings import StringDictionary
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.utils.metrics import QueryStats

from .support import assert_rows_equal

FORMS = ["coded", "plain"]
RG = 3000        # rows a row group
BATCH = 4096     # rows a scan batch: batch 0 spans row groups 0 and 1
N = 4 * RG


def _lineitem(nulls=False):
    """Q1's columns; row group 1 reverses the order in which the flags
    first occur, and row group 2 brings a flag no other group has."""
    rng = np.random.default_rng(38)
    flag = rng.choice(["A", "N", "R"], N).astype(object)
    flag[RG:RG + 4] = ["R", "N", "A", "R"]
    flag[2 * RG:3 * RG] = rng.choice(["N", "X"], RG)
    status = rng.choice(["O", "F"], N).astype(object)
    if nulls:
        flag[::97] = None
        status[5::89] = None
    return pa.table({
        "l_returnflag": pa.array(list(flag), type=pa.string()),
        "l_linestatus": pa.array(list(status), type=pa.string()),
        "l_quantity": pa.array(rng.integers(1, 51, N).astype(np.float64)),
        "l_extendedprice": pa.array(rng.uniform(900.0, 9e4, N)),
        "l_discount": pa.array(rng.integers(0, 11, N) / 100.0),
    })


def _write(tmp_path, table, form, name="t"):
    path = str(tmp_path / f"{name}_{form}.parquet")
    pq.write_table(table, path, row_group_size=RG,
                   use_dictionary=(form == "coded"))
    return path


def _read(sess, path):
    sess.conf.set("spark.rapids.tpu.sql.batchSizeRows", BATCH)
    return sess.read_parquet(path)


def _q1(df):
    return (df.group_by("l_returnflag", "l_linestatus")
            .agg(F.sum(F.col("l_quantity")).alias("sum_qty"),
                 F.sum(F.col("l_extendedprice")).alias("sum_price"),
                 F.avg(F.col("l_discount")).alias("avg_disc"),
                 F.count_star().alias("n"))
            .sort("l_returnflag", "l_linestatus"))


def _q1_pandas(table):
    pdf = table.to_pandas()
    g = pdf.groupby(["l_returnflag", "l_linestatus"], dropna=False)
    out = []
    for (f, s), grp in g:
        out.append((None if isinstance(f, float) else f,
                    None if isinstance(s, float) else s,
                    float(grp.l_quantity.sum()),
                    float(grp.l_extendedprice.sum()),
                    float(grp.l_discount.mean()), len(grp)))
    return out


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("nulls", [False, True], ids=["no_nulls", "nulls"])
def test_q1_shape_answers_what_pandas_answers(fresh_session, tmp_path,
                                              form, nulls):
    """groupBy(flag, status) with sums, an average and a count: the same
    groups and values in both forms, NULL keys a group of their own."""
    table = _lineitem(nulls)
    df = _read(fresh_session, _write(tmp_path, table, form))
    with QueryStats.scoped() as qs:
        got = _q1(df).collect()
    assert_rows_equal(got, _q1_pandas(table), approx_float=True)
    batches = math.ceil(N / BATCH)
    assert qs.page_coded_keys == (2 * batches if form == "coded" else 0)


@pytest.mark.parametrize("form", FORMS)
def test_the_page_coded_form_sorts_as_the_plain_one(fresh_session, tmp_path,
                                                   form):
    """ORDER BY the string keys after the aggregate: the same rows in the
    same order, whatever order the pages' dictionaries hold."""
    table = _lineitem()
    want = _q1(_read(fresh_session, _write(tmp_path, table, "plain",
                                           "want"))).collect()
    got = _q1(_read(fresh_session, _write(tmp_path, table, form))).collect()
    assert [r[:2] for r in got] == [r[:2] for r in want]
    assert_rows_equal(got, want, approx_float=True, ignore_order=False)


@pytest.mark.parametrize("form", FORMS)
def test_a_batch_spans_two_row_groups(tmp_path, form):
    """The scan cuts the batches the plain read cuts; batch 0 holds the
    codes of two pages whose dictionaries differ in content and order, and
    reads back the file's strings."""
    table = _lineitem(nulls=True)
    path = _write(tmp_path, table, form)
    src = ParquetSource(path, batch_rows=BATCH, num_threads=0)
    tables = list(src())
    assert [t.num_rows for t in tables] == [BATCH, BATCH, N - 2 * BATCH]
    first = from_arrow(tables[0]).columns[0]
    if form == "coded":
        assert isinstance(first, PageCodedStringColumn)
        dicts = [c.dictionary.to_pylist() for c in first.chunks]
        assert len(dicts) == 2 and dicts[0] != dicts[1]
    else:
        assert not isinstance(first, PageCodedStringColumn)
    assert first.capacity == BATCH and first.array.type == pa.string()
    got = [v for t in tables for v in
           from_arrow(t).columns[0].array.to_pylist()[:t.num_rows]]
    assert got == table.column("l_returnflag").to_pylist()


@pytest.mark.parametrize("form", FORMS)
def test_the_encode_maps_the_page_dictionary_as_hashing_would(tmp_path,
                                                              form):
    """Codes, validity and the dictionary's order equal the hashing
    encode's over the decoded strings, NULL rows included; the codes are
    int32 and the validity is None where no live row is NULL."""
    for nulls in (False, True):
        path = _write(tmp_path, _lineitem(nulls), form, f"n{nulls}")
        for t in ParquetSource(path, batch_rows=BATCH, num_threads=0)():
            col = from_arrow(t).columns[0]
            hashed, coded = StringDictionary(), StringDictionary()
            want, want_valid = hashed.encode(col.array)
            if form == "plain":
                assert want.dtype == np.int32
                continue
            got, valid = coded.encode_page_codes(col)
            assert got.dtype == np.int32 and len(got) == col.capacity
            # the same values; new ones in the pages' order, which is the
            # order of first occurrence in each row group
            assert sorted(coded._values) == sorted(hashed._values)
            live = np.arange(col.capacity) < t.num_rows
            if want_valid is None:  # a full batch: no padding
                want_valid = live
            if valid is None:
                assert not nulls and t.column(0).null_count == 0
                valid = live
            assert (valid == want_valid).all()
            values = np.array(coded._values, dtype=object)
            assert (values[got[valid]] ==
                    np.array(hashed._values, dtype=object)[want[valid]]).all()
            assert (got[~valid] == 0).all()


@pytest.mark.parametrize("form", FORMS)
def test_a_chunk_that_fell_back_to_plain_reads_as_plain(fresh_session,
                                                        tmp_path, form):
    """A writer that ran out of dictionary page leaves chunks of more
    bytes a value than a PLAIN value: the footer says so, and the column
    reads as strings; a near-unique column likewise."""
    rng = np.random.default_rng(5)
    n = 2 * RG
    table = pa.table({
        "comment": pa.array([f"{w} lorem ipsum {i}" for i, w in
                             enumerate(rng.choice(["ab", "cd"], n))]),
        "flag": pa.array(rng.choice(["A", "N"], n)),
    })
    path = str(tmp_path / f"fb_{form}.parquet")
    pq.write_table(table, path, row_group_size=RG,
                   use_dictionary=(form == "coded"),
                   dictionary_pagesize_limit=256)
    assert page_coded_columns(pq.ParquetFile(path), None) == \
        (["flag"] if form == "coded" else [])
    src = ParquetSource(path, batch_rows=BATCH, num_threads=0)
    for t in src():
        batch = from_arrow(t)
        assert type(batch.columns[0]) is HostStringColumn
        assert isinstance(batch.columns[1], PageCodedStringColumn) == \
            (form == "coded")
    df = _read(fresh_session, path)
    got = df.group_by("comment").agg(F.count_star().alias("n")).collect()
    assert len(got) == n


@pytest.mark.parametrize("form", FORMS)
def test_filter_join_payload_and_collect(fresh_session, tmp_path, form):
    """Q3's shape over a page-coded segment column: the string filter, a
    broadcast join whose payload is a page-coded column that the aggregate
    then groups by, and a collect() of a page-coded column."""
    rng = np.random.default_rng(3)
    nc, no = 2000, 3 * RG
    segs = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
    cust = pa.table({
        "c_custkey": pa.array(np.arange(nc, dtype=np.int64)),
        "c_mktsegment": pa.array(rng.choice(segs, nc)),
    })
    orders = pa.table({
        "o_custkey": pa.array(rng.integers(0, nc, no).astype(np.int64)),
        "o_price": pa.array(rng.uniform(1.0, 100.0, no)),
        "o_prio": pa.array(rng.choice(["1-URGENT", "2-HIGH", "3-LOW"], no)),
    })
    sess = fresh_session
    c = _read(sess, _write(tmp_path, cust, form, "cust"))
    o = _read(sess, _write(tmp_path, orders, form, "orders"))
    pc_, po = cust.to_pandas(), orders.to_pandas()
    m = po.merge(pc_, left_on="o_custkey", right_on="c_custkey")

    q3 = (c.where(F.col("c_mktsegment") == "BUILDING")
          .join(o, on=F.col("c_custkey") == F.col("o_custkey"))
          .group_by("o_prio").agg(F.sum(F.col("o_price")).alias("s")))
    want = [(k, float(v)) for k, v in
            m[m.c_mktsegment == "BUILDING"].groupby("o_prio")
            .o_price.sum().items()]
    assert_rows_equal(q3.collect(), want, approx_float=True)

    by_seg = (o.join(c, on=F.col("o_custkey") == F.col("c_custkey"))
              .group_by("c_mktsegment")
              .agg(F.count_star().alias("n")))
    with QueryStats.scoped() as qs:
        got = by_seg.collect()
    assert_rows_equal(got, [(k, int(v)) for k, v in
                            m.groupby("c_mktsegment").size().items()])
    # the segment reached the aggregate as the join's device codes
    assert qs.page_coded_keys == 0

    assert o.select("o_prio", "o_custkey").collect() == \
        list(zip(po.o_prio, po.o_custkey))


@pytest.mark.parametrize("form", FORMS)
def test_the_counter_counts_page_coded_key_columns(fresh_session, tmp_path,
                                                   form):
    """Two page-coded keys add 2 a batch; a key the scan read as strings
    adds nothing, in either form."""
    table = _lineitem()
    plain_key = table.append_column(
        "uniq", pa.array([f"row-{i:08d}" for i in range(N)]))
    df = _read(fresh_session, _write(tmp_path, plain_key, form))
    batches = math.ceil(N / BATCH)
    with QueryStats.scoped() as qs:
        df.group_by("l_returnflag", "l_linestatus").agg(
            F.count_star().alias("n")).collect()
    assert qs.page_coded_keys == (2 * batches if form == "coded" else 0)
    with QueryStats.scoped() as qs:
        got = df.group_by("uniq").agg(F.count_star().alias("n")).collect()
    assert len(got) == N and qs.page_coded_keys == 0


@pytest.mark.parametrize("form", FORMS)
def test_page_coded_keys_never_take_the_hashing_encode(fresh_session,
                                                       tmp_path, monkeypatch,
                                                       form):
    """The page-coded form never reaches ``StringDictionary.encode`` (the
    row hash whose NULL path went through float64 indices); the plain form
    does, and its codes are int32."""
    seen = []
    real = StringDictionary.encode

    def spy(self, arr):
        if form == "coded":
            raise AssertionError("page codes went through the row hash")
        codes, valid = real(self, arr)
        seen.append(codes.dtype)
        return codes, valid

    monkeypatch.setattr(StringDictionary, "encode", spy)
    table = _lineitem(nulls=True)
    df = _read(fresh_session, _write(tmp_path, table, form))
    assert_rows_equal(_q1(df).collect(), _q1_pandas(table), approx_float=True)
    assert seen == ([] if form == "coded" else [np.int32] * len(seen))
    assert form == "coded" or seen

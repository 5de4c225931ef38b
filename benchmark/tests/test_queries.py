"""Every query file: its run on the engine equals its plain reference at
SF0.01, and its least bytes are the schema's widths times the rows."""

import numpy as np
import pytest

import run as bench_run
from harness import compare, sources

QUERIES = [("tpch", q) for q in ("q1", "q3", "q6", "q13")] + \
          [("tpcds", q) for q in ("q3", "q42", "q52", "q55")]


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    import pyarrow.parquet as pq
    import spark_rapids_tpu as srt
    sess = srt.Session.get_or_create()
    out = {}
    for suite in ("tpch", "tpcds"):
        gen = sources.load_module([bench_run.HERE], "datagen", suite + ".py")
        # a scale at which the star queries have rows to return
        sf = 0.01 if suite == "tpch" else 0.1
        paths = gen.gen(sf, 2**31 + 7, str(tmp_path_factory.mktemp(suite)))
        out[suite] = (gen, sf,
                      {t: sess.read_parquet(p) for t, p in paths.items()},
                      {t: pq.read_table(p).to_pandas()
                       for t, p in paths.items()})
    return out


@pytest.mark.parametrize("suite,name", QUERIES)
def test_run_equals_reference(suite, name, world):
    q = sources.load_module([bench_run.HERE], "queries", suite, name + ".py")
    gen, sf, dfs, pds = world[suite]
    rng = np.random.default_rng([3, len(name)])
    nonempty = 0
    for _ in range(3):
        p = q.params(rng)
        got, want = q.run(dfs, p), q.reference(pds, p)
        assert compare.rows_rel_err(got, want) < 1e-12, (p, got[:3], want[:3])
        nonempty += bool(want)
    assert nonempty, "every parameter set drawn gave an empty answer"


@pytest.mark.parametrize("suite,name", QUERIES)
def test_min_bytes_is_schema_times_rows(suite, name, world):
    q = sources.load_module([bench_run.HERE], "queries", suite, name + ".py")
    gen = world[suite][0]
    nrows = gen.rows(1.0)
    want = sum(nrows[t] * sum(gen.SCHEMA[t][c] for c in cols)
               for t, cols in q.TABLES.items())
    assert q.min_bytes(nrows, gen.SCHEMA, 0) == want
    assert q.min_bytes(nrows, gen.SCHEMA, 10) == want \
        + 10 * q.RESULT_ROW_BYTES
    # every column named exists in the generator's schema
    for t, cols in q.TABLES.items():
        assert set(cols) <= set(gen.SCHEMA[t])


def test_schema_lists_what_the_generator_writes(world):
    for suite, (gen, _, _, pds) in world.items():
        for t, df in pds.items():
            assert list(df.columns) == list(gen.SCHEMA[t]), (suite, t)
            assert len(df) == gen.rows(world[suite][1])[t]


def test_tpch_sf1_q3_least_bytes():
    gen = sources.load_module([bench_run.HERE], "datagen", "tpch.py")
    q3 = sources.load_module([bench_run.HERE], "queries", "tpch", "q3.py")
    # lineitem 4 columns (8+8+8+4), orders (8+8+4+8), customer (8+4)
    assert q3.min_bytes(gen.rows(1.0), gen.SCHEMA, 10) == (
        6_001_215 * 28 + 1_500_000 * 28 + 150_000 * 12 + 10 * 28)

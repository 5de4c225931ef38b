"""The same seed gives the same data and parameter pools, another seed
others; a table does not depend on which tables are made beside it."""

import hashlib

import pyarrow.parquet as pq
import pytest

import run as bench_run
from harness import sources

BIG = 2**31 + 12345  # seeds pass 32 signed bits


def digest(paths):
    h = hashlib.sha256()
    for t in sorted(paths):
        tbl = pq.read_table(paths[t])
        for col in tbl.column_names:
            h.update(repr(tbl[col].to_pylist()[:2000]).encode())
        h.update(str(tbl.num_rows).encode())
    return h.hexdigest()


@pytest.mark.parametrize("suite", ["tpch", "tpcds"])
def test_data_follows_the_seed(suite, tmp_path):
    gen = sources.load_module([bench_run.HERE], "datagen", suite + ".py")
    a = digest(gen.gen(0.01, BIG, str(tmp_path / "a")))
    b = digest(gen.gen(0.01, BIG, str(tmp_path / "b")))
    c = digest(gen.gen(0.01, BIG + 1, str(tmp_path / "c")))
    assert a == b and a != c


def test_a_table_is_the_same_alone_and_among_others(tmp_path):
    gen = sources.load_module([bench_run.HERE], "datagen", "tpch.py")
    alone = gen.gen(0.01, 5, str(tmp_path / "a"), ["orders"])
    among = gen.gen(0.01, 5, str(tmp_path / "b"))
    assert sorted(alone) == ["orders"]
    assert digest(alone) == digest({"orders": among["orders"]})
    with pytest.raises(ValueError, match="no table"):
        gen.gen(0.01, 5, str(tmp_path / "c"), ["nope"])


@pytest.mark.parametrize("workload", ["tpch_sf1.joins", "tpcds_sf1.stars"])
def test_pools_follow_the_seed(workload, tiny_root):
    cell = bench_run.Cell(workload, tiny_root)
    a, b, c = cell.pools(BIG), cell.pools(BIG), cell.pools(BIG + 1)
    assert a == b and a != c
    assert all(len(a[q]) == cell.pool for q in cell.mix)
    # every value drawn lies in the range the query's file lists
    for q, sets in a.items():
        for p in sets:
            for name, value in p.items():
                rng = cell.queries[q].PARAMS[name]
                if isinstance(value, str) and name != "date":
                    assert value in rng
                else:
                    assert rng[0] <= value <= rng[-1]

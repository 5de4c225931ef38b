"""The four-chip cell ``tpch_sf1_ici4.joins`` on a four-device CPU mesh at
SF0.01: its two query files under the configuration's confs equal their
pandas twins under the configuration's limits, the fragment ran on the mesh,
and a (query, parameter set) compiles once: on its first run, and once more
where a capacity overflowed and the fragment was retried at 4x."""

import json
import os

import numpy as np
import pytest

import run as bench_run
from conftest import REPO, TINY_SF
from harness import compare, sources

CELL = "tpch_sf1_ici4.joins"
SEEDS = (2**31 + 11, 77)
N_DEV = 4
JOIN_ROWS = "spark.rapids.tpu.shuffle.ici.joinOutputRows"


@pytest.fixture(scope="module")
def cell():
    return bench_run.Cell(CELL)


@pytest.fixture(scope="module")
def sess(cell):
    import jax
    import spark_rapids_tpu as srt
    from jax.sharding import Mesh
    # benchmark/conftest.py asks for four virtual devices; fewer is a
    # fault of the set-up, and these cases must not pass by not running
    assert len(jax.devices()) >= N_DEV, (
        f"{len(jax.devices())} device(s): XLA_FLAGS names another count, "
        f"or a backend was up before benchmark/conftest.py")
    srt.Session.reset()
    s = srt.Session.get_or_create(settings=dict(cell.config["confs"]))
    s.set_mesh(Mesh(np.array(jax.devices()[:N_DEV]), ("data",)))
    yield s
    srt.Session.reset()


@pytest.fixture(scope="module")
def worlds(cell, sess, tmp_path_factory):
    """Per seed: the engine's tables, the twin's, the pools."""
    import pyarrow.parquet as pq
    out = {}
    for seed in SEEDS:
        paths = cell.datagen.gen(TINY_SF, seed,
                                 str(tmp_path_factory.mktemp(f"s{seed}")),
                                 sorted(cell.columns))
        out[seed] = ({t: sess.read_parquet(paths[t]) for t in cell.columns},
                     {t: pq.read_table(paths[t]).to_pandas()
                      for t in cell.columns},
                     cell.pools(seed))
    return out


def _stats():
    from spark_rapids_tpu.utils.metrics import QueryStats
    return QueryStats.scoped()


def test_the_configuration_is_tpch_sf1_with_two_confs(cell):
    with open(os.path.join(REPO, "benchmark", "configs",
                           "tpch_sf1.json")) as f:
        base = json.load(f)
    cfg = cell.config
    for key in ("suite", "datagen", "sf", "query_set", "precision",
                "guarantees", "limits"):
        assert cfg[key] == base[key], key
    assert cfg["confs"] == {
        "spark.rapids.tpu.shuffle.mode": "ICI",
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1}
    assert cfg["assumed"][:len(base["assumed"])] == base["assumed"]
    assert cell.chips == N_DEV and cell.mix == ["q3", "q13"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("query", ["q3", "q13"])
def test_answers_equal_the_twin_and_ran_on_the_mesh(cell, sess, worlds,
                                                    query, seed):
    dfs, pds, pools = worlds[seed]
    q = cell.queries[query]
    params = pools[query][0]
    with _stats() as first:
        got = q.run(dfs, params)
    correct, failed, compared, _ = compare.judge(
        [(query, 0, got)], {(query, 0): q.reference(pds, params)},
        cell.config["limits"])
    assert correct and not failed, compared
    assert first.ici_fragments >= 1 and first.ici_overflow_retries == 0
    assert first.ici_exchange_bytes > 0
    per_device = {}
    for mset in sess.last_exec_context().metrics.values():
        for name, v in mset.values.items():
            if name.startswith("iciInputBytes."):
                per_device[name] = per_device.get(name, 0) + v
    assert len(per_device) == N_DEV and min(per_device.values()) > 0
    assert sum(per_device.values()) == first.ici_feed_bytes

    # the second run of the same (query, set) traces and compiles nothing
    logged = []

    def on_duration(event, duration, fun_name=None, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            logged.append(fun_name)
    import jax
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    with _stats() as second:
        again = q.run(dfs, params)
    jax.monitoring.unregister_event_duration_listener(on_duration)
    assert second.compiles == 0 and logged == []
    assert second.ici_exchange_bytes == first.ici_exchange_bytes
    assert compare.rows_rel_err(again, got) == 0.0


def test_an_overflow_retry_compiles_once_more_and_is_cached_too(
        cell, sess, worlds):
    dfs, pds, pools = worlds[SEEDS[0]]
    q = cell.queries["q13"]
    params = pools["q13"][0]
    want = q.run(dfs, params)  # the unforced shapes are compiled by now
    # a join expansion of 64 rows a device overflows; the fragment is
    # re-lowered at 4x until it fits, under other cache keys
    sess.conf.set("spark.rapids.tpu.shuffle.ici.overflowRetries", 8)
    sess.conf.set(JOIN_ROWS, 64)
    try:
        with _stats() as first:
            got = q.run(dfs, params)
        with _stats() as second:
            again = q.run(dfs, params)
    finally:
        sess.conf.unset(JOIN_ROWS)
        sess.conf.unset("spark.rapids.tpu.shuffle.ici.overflowRetries")
    assert first.ici_overflow_retries >= 1 and first.compiles >= 1
    assert second.ici_overflow_retries == first.ici_overflow_retries
    assert second.compiles == 0
    assert compare.rows_rel_err(got, want) == 0.0
    assert compare.rows_rel_err(again, want) == 0.0


def test_the_reader_leaves_the_mesh_metrics_out_where_there_is_no_field():
    """The parent commit has no ``ici_*`` field in ``QueryStats``: the
    seven metric files then read nothing and raise nothing."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) == 7 and {m["layer"] for m in mine} == {"mesh"}
    ob = sources.Observed(setup_s=1.0, window_s=40.0, latencies=[1.0, 2.0],
                          qs_delta={"blocking_fetches": 9}, memory={},
                          device_kind="cpu", platform="cpu")
    for m in mine:
        spec = sources.load_json([bench_run.HERE], "metrics",
                                 m["name"] + ".json")
        assert spec["kind"] == "querystats_delta"
        assert sources.reader(m["name"], spec)(spec, ob) is None
        ob2 = sources.Observed(
            setup_s=1.0, window_s=40.0, latencies=[1.0, 2.0],
            qs_delta={spec["field"]: 8.0}, memory={}, device_kind="cpu",
            platform="cpu")
        assert sources.reader(m["name"], spec)(spec, ob2) > 0

"""Structured query tracing: span trees, profiled EXPLAIN, trace export.

Covers the acceptance surface of the tracing layer (ISSUE 2): the span
tree mirrors the physical plan, the Chrome-trace JSON round-trips and
validates as trace events, profiled explain carries rows/bytes/time for
every operator, the tracing-off path stays on the fast path, and
QueryStats is query-scoped (concurrent queries don't cross-account).
"""

import json
import threading

import numpy as np
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.sql import functions as F

TRACE_KEY = "spark.rapids.tpu.sql.trace.enabled"
DIR_KEY = "spark.rapids.tpu.sql.trace.dir"
RECORDER_KEY = "spark.rapids.tpu.recorder.enabled"


@pytest.fixture()
def sess():
    s = srt.Session.get_or_create()
    yield s
    s.conf.unset(TRACE_KEY)
    s.conf.unset(DIR_KEY)
    s.conf.unset(RECORDER_KEY)


def _tpch_slice(sess, n=20000, seed=11):
    """A Q6/Q1-flavored slice: scan -> filter -> grouped agg."""
    rng = np.random.default_rng(seed)
    df = sess.create_dataframe({
        "l_quantity": rng.integers(1, 51, n).astype(np.float64),
        "l_extendedprice": (rng.random(n) * 100000).round(2),
        "l_discount": rng.integers(0, 11, n).astype(np.float64) / 100,
    })
    return (df.where((F.col("l_discount") >= 0.05)
                     & (F.col("l_quantity") < 24))
            .group_by((F.col("l_quantity") % 4).cast("int").alias("b"))
            .agg(F.sum(F.col("l_extendedprice")).alias("rev"),
                 F.count_star().alias("n")))


def _run_traced(sess, q):
    sess.conf.set(TRACE_KEY, True)
    try:
        q.collect()
    finally:
        sess.conf.unset(TRACE_KEY)
    tr = sess.last_trace()
    assert tr is not None
    return tr


# ---------------------------------------------------------------------------------
# span tree structure
# ---------------------------------------------------------------------------------

def test_span_tree_matches_physical_plan(sess):
    tr = _run_traced(sess, _tpch_slice(sess))
    phys = sess._last_phys

    def plan_shape(node):
        return (node.op_id, type(node).__name__,
                [plan_shape(c) for c in node.children])

    def tree_shape(entry):
        return (entry["op_id"], entry["name"],
                [tree_shape(c) for c in entry["children"]])

    # the first root IS the plan; extra roots (if any) are runtime ops
    assert tree_shape(tr.roots[0]) == plan_shape(phys)
    # every plan operator produced at least one operator span event
    op_ids_with_events = {e[0] for e in tr.events if e[2] == "operator"}

    def walk_ids(node):
        yield node.op_id
        for c in node.children:
            yield from walk_ids(c)

    for op_id in walk_ids(phys):
        assert op_id in op_ids_with_events, f"no operator span for {op_id}"


def test_span_tree_carries_operator_metrics(sess):
    tr = _run_traced(sess, _tpch_slice(sess))

    def walk(entry):
        yield entry
        for c in entry["children"]:
            yield from walk(c)

    for entry in walk(tr.roots[0]):
        m = entry["metrics"]
        assert m.get("outputRows", 0) > 0, entry["op_id"]
        assert m.get("outputBatches", 0) >= 1
        assert m.get("produceTimeS", 0) > 0
    # the absorbed QueryStats snapshot rides on the root attrs
    assert "blocking_fetches" in tr.attrs
    assert "compiles" in tr.attrs


# ---------------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------------

def test_trace_json_roundtrips_and_validates(sess):
    tr = _run_traced(sess, _tpch_slice(sess))
    data = json.loads(json.dumps(tr.to_chrome()))
    evs = data["traceEvents"]
    assert evs, "no trace events"
    cats = set()
    for e in evs:
        assert e["ph"] in ("X", "M", "i")
        assert isinstance(e["name"], str) and e["name"]
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
            cats.add(e.get("cat"))
    # the phases the span model promises
    assert "query" in cats and "operator" in cats and "phase" in cats
    assert "fetch" in cats
    # the query-level event spans the run and carries the stats snapshot
    q = next(e for e in evs if e.get("cat") == "query")
    assert q["dur"] > 0 and q["args"]["blocking_fetches"] >= 1
    # every operator event fits inside the query window (with slack for
    # float rounding)
    for e in evs:
        if e.get("cat") == "operator":
            assert e["ts"] + e["dur"] <= q["dur"] * 1.05 + 1000


def test_trace_dir_writes_one_file_per_query(sess, tmp_path):
    sess.conf.set(TRACE_KEY, True)
    sess.conf.set(DIR_KEY, str(tmp_path))
    try:
        _tpch_slice(sess).collect()
        _tpch_slice(sess, seed=12).collect()
    finally:
        sess.conf.unset(TRACE_KEY)
        sess.conf.unset(DIR_KEY)
    # the every-query dump writes query-*.trace.json; the flight
    # recorder dumps what retention keeps as capture-*.trace.json
    # into the same dir (tested in test_recorder.py)
    files = sorted(p for p in tmp_path.glob("*.trace.json")
                   if not p.name.startswith("capture-"))
    assert len(files) == 2
    for f in files:
        data = json.loads(f.read_text())
        assert data["traceEvents"]
        assert data["spanTree"]


# ---------------------------------------------------------------------------------
# profiled EXPLAIN
# ---------------------------------------------------------------------------------

def test_profiled_explain_annotates_every_operator(sess):
    q = _tpch_slice(sess)
    out = q.explain_profiled()
    phys = sess._last_phys
    n_ops = 0

    def walk(node):
        nonlocal n_ops
        n_ops += 1
        for c in node.children:
            walk(c)

    walk(phys)
    # one metrics line per operator, each with rows/bytes/time
    metric_lines = [ln for ln in out.splitlines() if "rows=" in ln]
    assert len(metric_lines) >= n_ops
    annotated = [ln for ln in metric_lines if "(not executed)" not in ln]
    assert len(annotated) >= n_ops
    for ln in annotated:
        assert "bytes=" in ln and "time=" in ln and "batches=" in ln
    # the tree itself is rendered too
    assert "TpuScan" in out and "TpuHashAggregate" in out


def test_profiled_explain_mode_prints(sess, capsys):
    _tpch_slice(sess).explain("profiled")
    out = capsys.readouterr().out
    assert "rows=" in out and "TpuScan" in out


def test_profiled_explain_without_query(fresh_session):
    assert "no query" in fresh_session.profiled_explain()


# ---------------------------------------------------------------------------------
# tracing-off fast path
# ---------------------------------------------------------------------------------

def test_tracing_off_stays_on_fast_path(fresh_session):
    from spark_rapids_tpu.utils import tracing
    # the flight recorder (default on) arms tracing for every query;
    # this test is about the FULLY-off fast path, so disarm it too
    fresh_session.conf.set(RECORDER_KEY, False)
    q = _tpch_slice(fresh_session)
    assert tracing.active() is None
    q.collect()
    # no trace captured, no active trace leaked
    assert fresh_session.last_trace() is None
    assert tracing.active() is None
    # with no trace a span still times (the account and the profiler
    # read it) and leaves no event; record/mark are no-ops
    with tracing.span("x", "op:y") as sp:
        pass
    assert sp.dur >= 0.0
    tracing.record("x", "y", "phase", 0.0, 1.0)  # no-op, no error
    tracing.mark("x", "y")


def test_trace_scope_does_not_leak_across_queries(sess):
    # disarm the recorder: with it on, every query is traced (by
    # design) and last_trace legitimately moves on
    sess.conf.set(RECORDER_KEY, False)
    tr1 = _run_traced(sess, _tpch_slice(sess))
    # an untraced query afterwards must not disturb the captured trace
    _tpch_slice(sess, seed=13).collect()
    assert sess.last_trace() is tr1
    n_events = len(tr1.events)
    _tpch_slice(sess, seed=14).collect()
    assert len(tr1.events) == n_events


def test_trace_spans_cross_pipeline_threads(sess):
    """With the async pipeline on, worker threads run in a copied context
    and their stage/wait spans join the query's trace."""
    sess.conf.set("spark.rapids.tpu.sql.pipeline.depth", 2)
    sess.conf.set("spark.rapids.tpu.sql.batchSizeRows", 4096)
    try:
        tr = _run_traced(sess, _tpch_slice(sess, n=30000))
    finally:
        sess.conf.unset("spark.rapids.tpu.sql.pipeline.depth")
        sess.conf.unset("spark.rapids.tpu.sql.batchSizeRows")
    cats = {e[2] for e in tr.events}
    assert "pipeline" in cats, "worker-thread spans missing from trace"
    # events landed on more than one thread lane and each lane is named
    tids = {e[5] for e in tr.events}
    assert len(tids) > 1
    names = [e for e in tr.to_chrome()["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert any("pipeline" in e["args"]["name"] for e in names)
    # the driving thread's waits were resolved through the workers: the
    # events name the thread waited on, the stats split the wait
    from spark_rapids_tpu.utils.tracing import RESOLVED_TERMS
    assert any(e[1] == "pipeline:wait" and (e[6] or {}).get("on")
               == "srt-pipeline-stage" for e in tr.events)
    parts = [tr.attrs[f"acct_h2d_{t}_s"] for t in RESOLVED_TERMS]
    # the snapshot rounds each field to a tenth of a millisecond
    assert sum(parts) == pytest.approx(tr.attrs["acct_h2d_wait_s"],
                                       abs=1e-3)
    assert sum(parts) > 0


def test_trace_event_cap_drops_not_grows(sess):
    sess.conf.set(TRACE_KEY, True)
    sess.conf.set("spark.rapids.tpu.sql.trace.maxEvents", 5)
    try:
        _tpch_slice(sess).collect()
    finally:
        sess.conf.unset(TRACE_KEY)
        sess.conf.unset("spark.rapids.tpu.sql.trace.maxEvents")
    tr = sess.last_trace()
    # at most maxEvents stored + the ONE forced trace:events_dropped
    # mark (the only event allowed past the cap): a truncated trace is
    # visibly truncated on the timeline, not just in otherData
    assert len(tr.events) <= 5 + 1
    assert tr.dropped > 0
    marks = [e for e in tr.events if e[1] == "trace:events_dropped"]
    assert len(marks) == 1
    assert marks[0][6]["max_events"] == 5
    assert tr.to_chrome()["otherData"]["dropped_events"] == tr.dropped


# ---------------------------------------------------------------------------------
# QueryStats scoping (contextvars)
# ---------------------------------------------------------------------------------

def test_querystats_scoped_concurrent_queries():
    import jax.numpy as jnp

    from spark_rapids_tpu.utils.metrics import QueryStats, fetch

    before = QueryStats.process().blocking_fetches
    counts = {}
    barrier = threading.Barrier(2)

    def worker(name, n):
        with QueryStats.scoped() as s:
            barrier.wait(timeout=10)
            for _ in range(n):
                fetch(jnp.ones((8,)))
            counts[name] = s.blocking_fetches

    t1 = threading.Thread(target=worker, args=("a", 3))
    t2 = threading.Thread(target=worker, args=("b", 5))
    t1.start(); t2.start(); t1.join(); t2.join()
    # each scope saw exactly its own fetches — no cross-accounting
    assert counts == {"a": 3, "b": 5}
    # and the process aggregate kept the cumulative total
    assert QueryStats.process().blocking_fetches == before + 8


def test_querystats_scope_folds_into_process():
    import jax.numpy as jnp

    from spark_rapids_tpu.utils.metrics import QueryStats, fetch

    before = QueryStats.process().snapshot()
    with QueryStats.scoped() as s:
        fetch(jnp.arange(4))
        assert s.blocking_fetches == 1
        assert QueryStats.get() is s
    after = QueryStats.process().snapshot()
    assert after["blocking_fetches"] == before["blocking_fetches"] + 1
    assert after["fetch_bytes"] > before["fetch_bytes"]
    assert QueryStats.get() is QueryStats.process()


def test_querystats_nested_scopes_fold_outward():
    import jax.numpy as jnp

    from spark_rapids_tpu.utils.metrics import QueryStats, fetch

    with QueryStats.scoped() as outer:
        with QueryStats.scoped() as inner:
            fetch(jnp.arange(4))
            assert inner.blocking_fetches == 1
            assert outer.blocking_fetches == 0
        assert outer.blocking_fetches == 1

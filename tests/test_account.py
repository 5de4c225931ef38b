"""The per-query host-time account (utils/tracing.account), the span
vocabulary on the profiler's clock, program names, and the upload
counters.

The account's contract: on the driving thread every second of a query
belongs to exactly one of nine terms, written once per query into
``QueryStats.acct_*_s``, and the nine sum to ``query_wall_s``.  What a
worker thread waits moves the all-thread sums (``fetch_wait_s``,
``h2d_wait_s``) and never the account.  The driving thread's
``h2d_wait`` is resolved through the producer threads it waited on into
seven parts (``acct_h2d_<term>_s``) that sum to it.
"""

import contextvars
import queue
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.models import tpcds, tpch_suite
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.utils import metrics as M
from spark_rapids_tpu.utils import recorder, tracing
from spark_rapids_tpu.utils.metrics import QueryStats

DEPTH_KEY = "spark.rapids.tpu.sql.pipeline.depth"
ACCT = [f"acct_{t}_s" for t in tracing.ACCOUNT_TERMS]
RESOLVED = [f"acct_h2d_{t}_s" for t in tracing.RESOLVED_TERMS]


@pytest.fixture(scope="module")
def dbs(session, tmp_path_factory):
    h = str(tmp_path_factory.mktemp("acct_tpch"))
    d = str(tmp_path_factory.mktemp("acct_tpcds"))
    return {"tpch": tpch_suite.load_db(session, 0.002, h),
            "tpcds": tpcds.load_db(session, 0.01, d)}


def _run(query, dbs):
    suite, name = query
    mod = tpch_suite if suite == "tpch" else tpcds
    return mod.QUERIES[name][0](dbs[suite])


def _closed(st, rel=0.01):
    terms = {k: getattr(st, k) for k in ACCT}
    assert all(v >= 0.0 for v in terms.values()), terms
    assert st.query_wall_s > 0.0
    assert sum(terms.values()) == pytest.approx(st.query_wall_s, rel=rel)
    return terms


def _resolved(st):
    """The seven parts of the driving thread's h2d wait: each >= 0, and
    together the wait, to float rounding."""
    parts = {k: getattr(st, k) for k in RESOLVED}
    assert all(v >= 0.0 for v in parts.values()), parts
    assert sum(parts.values()) == pytest.approx(st.acct_h2d_wait_s,
                                                rel=1e-9, abs=1e-12)
    return parts


@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("query", [("tpch", "q3"), ("tpch", "q13"),
                                   ("tpcds", "ds_q42")],
                         ids=lambda q: "-".join(q))
def test_nine_terms_sum_to_the_wall(session, dbs, query, depth):
    session.conf.set(DEPTH_KEY, depth)
    try:
        _run(query, dbs)  # compiles land in the first run's account
        with QueryStats.scoped() as st:
            rows = _run(query, dbs)
    finally:
        session.conf.unset(DEPTH_KEY)
    assert rows
    terms = _closed(st)
    # a join query plans, runs programs and materialises rows
    assert terms["acct_plan_s"] > 0 and terms["acct_dispatch_s"] > 0
    assert terms["acct_result_s"] > 0 and terms["acct_host_exec_s"] > 0
    parts = _resolved(st)
    if depth:
        # the scans stage on workers: the driving thread waited for them,
        # and for what they did under their spans
        assert st.h2d_wait_s > 0
        assert sum(parts.values()) - parts["acct_h2d_handoff_s"] > 0


def test_first_run_charges_its_compiles_to_the_compile_term(session):
    df = session.create_dataframe(
        {"k": np.arange(5000) % 11, "v": np.arange(5000) * 1.25})
    with QueryStats.scoped() as st:
        # a literal no other test uses: this plan compiles here
        df.filter(F.col("v") > 17.0625).group_by("k") \
            .agg(F.sum(F.col("v")).alias("s")).collect()
    terms = _closed(st)
    assert st.compiles > 0
    assert terms["acct_compile_s"] == pytest.approx(st.compile_s, rel=1e-6)


def test_a_scalar_subquery_joins_its_parents_account(session, dbs):
    li = dbs["tpch"]["lineitem"]
    avg = F.scalar_subquery(li.agg(F.avg(F.col("l_quantity")).alias("a")))
    q = li.filter(F.col("l_quantity") > avg) \
        .agg(F.count_star().alias("n"))
    q.collect()
    import time
    t0 = time.perf_counter()
    with QueryStats.scoped() as st:
        assert q.collect()[0][0] > 0
    outer_wall = time.perf_counter() - t0
    _closed(st)
    # two executions (the subquery's, then the query's) under ONE wall:
    # were the child's wall added to the parent's, the sum would pass
    # what the caller saw go by
    assert st.query_wall_s <= outer_wall
    tr = session.last_trace()
    assert sum(1 for e in tr.events if e[1] == "result:concat") == 2
    assert sum(1 for e in tr.events if e[1] == "plan:subqueries") == 1


def _on_worker(fn):
    ctx = contextvars.copy_context()
    th = threading.Thread(target=lambda: ctx.run(fn))
    th.start()
    th.join()


def test_a_workers_fetch_moves_the_sum_and_not_the_account():
    with QueryStats.scoped() as st:
        with tracing.account(st):
            _on_worker(lambda: M.fetch(jnp.arange(1 << 16)))
            assert st.fetch_wait_s > 0 and st.blocking_fetches == 1
    assert st.acct_fetch_wait_s == 0.0
    _closed(st, rel=1e-6)
    with QueryStats.scoped() as st2:
        with tracing.account(st2):
            M.fetch(jnp.arange(1 << 16))
    assert st2.acct_fetch_wait_s == pytest.approx(st2.fetch_wait_s,
                                                  rel=1e-6)


def test_a_workers_pipeline_wait_moves_the_sum_and_not_the_account():
    import time

    from spark_rapids_tpu.runtime.pipeline import pipeline_map

    def slow(x):
        time.sleep(0.01)
        return x

    def consume():
        assert list(pipeline_map(range(4), slow, depth=2)) == [0, 1, 2, 3]

    with QueryStats.scoped() as st:
        with tracing.account(st):
            _on_worker(consume)
    assert st.h2d_wait_s > 0
    assert st.acct_h2d_wait_s == 0.0
    assert not any(_resolved(st).values())
    with QueryStats.scoped() as st2:
        with tracing.account(st2):
            consume()
    assert st2.acct_h2d_wait_s == pytest.approx(st2.h2d_wait_s, rel=1e-6)
    _closed(st2, rel=1e-6)
    # the worker slept under pipeline:stage while the consumer waited
    assert _resolved(st2)["acct_h2d_host_exec_s"] > 0


def test_a_two_level_wait_lands_on_the_decode_it_waited_for(monkeypatch):
    """consumer -> pipeline_map worker -> prefetch thread: the driving
    thread's pipeline:wait is resolved through the worker, whose own
    scan:wait is resolved through the prefetch thread's scan:decode."""
    from spark_rapids_tpu.io.parquet import decoded, next_prefetched
    from spark_rapids_tpu.runtime.pipeline import pipeline_map

    # tracing's clock, moved by hand: time passes only where the test
    # says, so the resolution is exact and no wait depends on the wall
    clock = [100.0]
    monkeypatch.setattr(tracing, "_pc", lambda: clock[0])
    # a wait reads its producer as it opens: the decode moves the clock
    # once the consumer waits on the worker and the worker on it
    in_wait = {"srt-pipeline-stage": threading.Event(),
               "srt-parquet-prefetch": threading.Event()}
    read = tracing._Producer.read

    def spy(self, now):
        in_wait[self.name].set()
        return read(self, now)

    monkeypatch.setattr(tracing._Producer, "read", spy)
    end = object()

    table = pa.table({"a": [1, 2]})

    def tables():  # under scan:decode on the prefetch thread
        assert all(e.wait(10) for e in in_wait.values())
        clock[0] += 1.5
        yield table

    def src():  # on the pipeline worker
        q = queue.Queue()

        def prefetch():
            try:
                for t in decoded(tables()):
                    q.put(t)
            finally:
                q.put(end)

        prod = tracing.start_producer(prefetch, "srt-parquet-prefetch")
        while (item := next_prefetched(q, prod)) is not end:
            yield item

    with QueryStats.scoped() as st:
        with tracing.query_trace("two-level") as tr:
            with tracing.account(st):
                assert list(pipeline_map(src(), lambda t: t, 1)) == [table]
    assert st.acct_h2d_wait_s == 1.5 and st.query_wall_s == 1.5
    assert _resolved(st) == dict.fromkeys(RESOLVED, 0.0) | {
        "acct_h2d_decode_s": 1.5}
    # the trace follows the hand-off: each wait names the thread it
    # waited on, with the parts
    waits = {e[1]: e[6] for e in tr.events if e[4] > 0
             and e[1] in ("pipeline:wait", "scan:wait")}
    assert waits["pipeline:wait"]["on"] == "srt-pipeline-stage"
    assert waits["scan:wait"]["on"] == "srt-parquet-prefetch"
    for args in waits.values():
        assert args["decode"] == 1.5 and args["handoff"] == 0.0


def test_a_wait_with_no_driving_account_open_charges_nothing():
    from spark_rapids_tpu.runtime.pipeline import pipeline_map
    assert tracing.start_producer(lambda: None, "srt-idle") is None
    with QueryStats.scoped() as st:
        with tracing.query_trace("no-account") as tr:
            assert list(pipeline_map(range(3), lambda x: x, 2)) == [0, 1, 2]
    assert st.acct_h2d_wait_s == 0.0
    assert not any(_resolved(st).values())
    assert not any("on" in (e[6] or {}) for e in tr.events)


def test_self_time_and_charged_intervals():
    import time
    with QueryStats.scoped() as st:
        with tracing.account(st):
            with tracing.span(None, "op:outer"):
                time.sleep(0.02)
                with tracing.span(None, "fetch:blocking", "fetch"):
                    time.sleep(0.03)
                # jax reports a compile when it ends: charged after the
                # fact, out of the open span's self time
                time.sleep(0.02)
                tracing.charge("compile", 0.02)
            with tracing.suspended():
                time.sleep(0.05)  # a stream's consumer: nobody's time
    assert st.acct_fetch_wait_s == pytest.approx(0.03, abs=0.01)
    assert st.acct_compile_s == pytest.approx(0.02)
    assert st.acct_host_exec_s == pytest.approx(0.02, abs=0.01)
    assert st.query_wall_s == pytest.approx(0.07, abs=0.015)
    _closed(st, rel=1e-6)


# ---------------------------------------------------------------------------------
# upload counters
# ---------------------------------------------------------------------------------

def test_upload_bytes_are_the_padded_device_bytes_and_zero_on_a_hit(
        session, tmp_path):
    from spark_rapids_tpu.batch import DeviceColumn
    from spark_rapids_tpu.cache import clear_query_cache
    from spark_rapids_tpu.plan.physical import ExecContext
    n = 3000
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.Table.from_pandas(pd.DataFrame({
        "a": np.arange(n, dtype=np.int64),
        "b": np.linspace(0.0, 1.0, n),
        "c": pd.array([None if i % 7 == 0 else i for i in range(n)],
                      dtype="Int32")}), preserve_index=False), path)
    df = session.read_parquet(path)
    phys = session._plan_physical(df._plan)
    ctx = ExecContext(session._tpu_conf(), device=session.device)
    with QueryStats.scoped() as st:
        batches = list(phys.execute(ctx))
    on_device = sum(
        c.data.nbytes + (c.valid.nbytes if c.valid is not None else 0)
        for b in batches for c in b.columns if isinstance(c, DeviceColumn))
    assert on_device > n * (8 + 8 + 4)  # padded past the rows
    assert st.upload_bytes == on_device
    assert st.uploads == 3 * len(batches) and st.upload_s > 0
    assert st.decode_s > 0

    session.conf.set("spark.rapids.tpu.sql.cache.enabled", True)
    clear_query_cache()
    try:
        df.collect()
        with QueryStats.scoped() as hit:
            df.collect()
    finally:
        session.conf.unset("spark.rapids.tpu.sql.cache.enabled")
        clear_query_cache()
    assert hit.cache_hits > 0
    assert hit.upload_bytes == 0 and hit.uploads == 0


# ---------------------------------------------------------------------------------
# the vocabulary, on the profiler's clock
# ---------------------------------------------------------------------------------

def _vocabulary():
    exact = {n for n, _, _ in tracing.SPANS if not n.endswith(":")}
    families = tuple(n for n, _, _ in tracing.SPANS if n.endswith(":"))
    return exact, families


def test_every_emitted_span_name_is_in_the_vocabulary(session, dbs):
    from spark_rapids_tpu.plan.physical import PROGRAM_NAMES
    _run(("tpch", "q3"), dbs)
    tr = session.last_trace()
    exact, families = _vocabulary()
    seen = set()
    for op_id, name, cat, *_ in tr.events:
        if cat in ("operator", "phase", "compile", "fusion", "mark"):
            continue  # named by class / MetricSet timer / jax
        seen.add(name)
        assert name in exact or name.startswith(families), name
        if name.startswith("program:"):
            assert name[len("program:"):] in PROGRAM_NAMES
    assert {"plan:overrides", "plan:fusion", "admit:semaphore",
            "scan:upload", "scan:decode", "result:arrow",
            "result:rows"} <= seen
    assert any(n.startswith("fetch:") for n in seen)
    assert any(n.startswith("program:") for n in seen)


def test_spans_land_in_a_profiler_session_under_stable_names(
        session, dbs, tmp_path):
    _run(("tpch", "q3"), dbs)  # compile outside the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _run(("tpch", "q3"), dbs)
    finally:
        jax.profiler.stop_trace()
    from tools import trace_report
    names = set()
    for plane, line, ev in trace_report.xplane_events(str(tmp_path)):
        if ":" in ev.name and re.fullmatch(r"[\w.#-]+:[\w.#-]+", ev.name):
            names.add(ev.name)
    assert {"plan:overrides", "scan:upload", "result:arrow"} <= names
    assert "fetch:blocking" in names or "fetch:async" in names
    ops = {n for n in names if n.startswith("op:")}
    assert "op:opTime" in ops and any(n.endswith("Exec") for n in ops)
    assert any(n.startswith("program:") for n in names)
    exact, families = _vocabulary()
    for n in names:
        if n.startswith(("$", "bench:")):
            continue
        assert "@" not in n and not re.search(r"\d{4,}", n), n
        assert n in exact or n.startswith(families), n


# ---------------------------------------------------------------------------------
# program names
# ---------------------------------------------------------------------------------

def test_program_names_are_the_vocabularys(session):
    from spark_rapids_tpu.plan import physical
    with pytest.raises(ValueError, match="PROGRAM_NAMES"):
        physical.program("f", lambda x: x)
    for name in physical.PROGRAM_NAMES:
        assert re.fullmatch(r"[a-z][a-z0-9_]*", name), name
    p = physical.program("stage", lambda x: x + 1)
    assert p.name == "stage" and int(p(jnp.int32(1))) == 2
    assert "stage" in p.lower(jnp.int32(1)).as_text()[:200]


def test_no_anonymous_program_compiles_over_q3(fresh_session, tmp_path):
    from spark_rapids_tpu.plan import physical
    from spark_rapids_tpu.plan.physical import clear_program_cache
    compiled = []

    def on_duration(event, duration, fun_name=None, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(fun_name)

    dfs = tpch_suite.load_db(fresh_session, 0.002, str(tmp_path))
    clear_program_cache()
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        # a batch size no other test uses: every program compiles here
        fresh_session.conf.set("spark.rapids.tpu.sql.batchSizeRows", 7168)
        tpch_suite.QUERIES["q3"][0](dfs)
    finally:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(on_duration)
    def bare(fun_name):  # jax reports "jit(<__name__>)"
        return re.sub(r"^jit\((.*)\)$", r"\1", fun_name or "")

    compiled = {bare(n) for n in compiled}
    ours = compiled & physical.PROGRAM_NAMES
    assert {"stage", "join_expand"} <= ours, compiled
    anonymous = {"f", "g", "h", "merge", "stage_fn", "_fin", "fin",
                 "batch_group", "batch_partials", "<lambda>"}
    assert not anonymous & compiled, compiled
    # the QueryTrace's compile events carry the same names
    tr = fresh_session.last_trace()
    traced = {bare(e[6]["fun_name"]) for e in tr.events
              if e[2] == "compile"}
    assert traced & physical.PROGRAM_NAMES


# ---------------------------------------------------------------------------------
# recorder.decompose judges the closed account
# ---------------------------------------------------------------------------------

@pytest.mark.parametrize("query", [("tpch", "q3"), ("tpcds", "ds_q42")],
                         ids=lambda q: "-".join(q))
def test_decompose_on_a_finished_trace_sums_to_its_wall(session, dbs,
                                                        query):
    _run(query, dbs)
    _run(query, dbs)
    tr = session.last_trace()
    live = recorder.decompose(tr.attrs, recorder._trace_events(tr))
    assert set(live) == set(recorder.TERMS)
    account = [t for t, _, _ in recorder._ACCOUNT_FIELDS]
    total = sum(live[t] for t in account)
    # the snapshot rounds each field to a tenth of a millisecond
    assert total == pytest.approx(tr.attrs["query_wall_s"], abs=1e-3)
    assert total == pytest.approx(tr.duration_s, rel=0.02, abs=2e-3)
    offline = recorder.decompose_chrome(tr.to_chrome())
    for t in recorder.TERMS:
        assert offline[t] == pytest.approx(live[t], abs=1e-4)
    # the seal stamped the same terms for explain_slow
    assert tr.attrs["perf_terms"]["host_exec"] == pytest.approx(
        live["host_exec"], abs=1e-4)


def test_the_docs_list_every_span_and_every_term():
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "docs", "observability.md")) as f:
        doc = f.read()
    table = doc[doc.index("<!-- SPANS:BEGIN"):doc.index("<!-- SPANS:END")]
    for name, term, _ in tracing.SPANS:
        assert f"| `{name}" in table and f"`{term}`" in table, name
    for term in tracing.ACCOUNT_TERMS:
        assert f"| `{term}` |" in doc, term
    resolved = doc[doc.index("### The wait resolved"):]
    for field in RESOLVED:
        metric = field.replace("acct_h2d_", "h2d_on_")[:-2] + "_pct"
        assert f"| `{field}` |" in resolved and f"`{metric}`" in resolved

"""Pipelined async executor (runtime/pipeline.py) regression tests.

Two contracts the pipeline must never break:
  (a) pipelined (depth>0) and serial (depth=0) execution produce
      identical results — the pipeline reorders WHEN work happens,
      never WHAT is computed;
  (b) depth is a hard bound on staged batches (HBM stays bounded).
"""

import threading
import time

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.runtime.pipeline import (effective_depth,
                                               pipeline_batches,
                                               pipeline_map)

# sync-heavy + scan-heavy representatives (q13/q16 are the PERF.md deep
# losers this pipeline targets; q1/q6 cover the fused-agg scan path)
SLICE = ["q1", "q3", "q6", "q13", "q16"]


# ---------------------------------------------------------------------------
# (a) pipelined == serial, byte for byte
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tpch(session, tmp_path_factory):
    from spark_rapids_tpu.models import tpch_suite
    out = str(tmp_path_factory.mktemp("tpch_pipeline"))
    return tpch_suite.load_db(session, 0.002, out)


@pytest.mark.parametrize("name", SLICE)
def test_pipelined_matches_serial_tpch(session, tpch, name):
    from spark_rapids_tpu.models import tpch_suite
    runner, _ = tpch_suite.QUERIES[name]
    results = {}
    for depth in (0, 2):
        session.conf.set("spark.rapids.tpu.sql.pipeline.depth", depth)
        try:
            results[depth] = runner(tpch)
        finally:
            session.conf.unset("spark.rapids.tpu.sql.pipeline.depth")
    assert results[0] == results[2], \
        f"{name}: depth=2 diverged from serial depth=0"


def test_pipelined_matches_serial_multibatch(session):
    """Small batches force a long pipeline (many staged uploads) through
    scan→filter→project→grouped agg→sort."""
    f = srt.functions
    rng = np.random.default_rng(11)
    df = session.create_dataframe({
        "k": rng.integers(0, 37, 20000).astype(np.int64),
        "v": rng.random(20000)})
    q = (df.filter(f.col("v") > 0.25)
           .select(f.col("k"), (f.col("v") * 3.0).alias("w"))
           .group_by("k").agg(f.sum(f.col("w")).alias("sw"))
           .sort(f.col("k")))
    out = {}
    for depth in (0, 3):
        session.conf.set("spark.rapids.tpu.sql.pipeline.depth", depth)
        session.conf.set("spark.rapids.tpu.sql.batchSizeRows", 2048)
        try:
            out[depth] = q.collect()
        finally:
            session.conf.unset("spark.rapids.tpu.sql.pipeline.depth")
            session.conf.unset("spark.rapids.tpu.sql.batchSizeRows")
    assert out[0] == out[3]


# ---------------------------------------------------------------------------
# (b) depth bounds
# ---------------------------------------------------------------------------

def test_pipeline_depth_bound():
    """At most `depth` staged items are ever live: a slot is reserved
    before the worker produces, so queue + in-flight <= depth."""
    lock = threading.Lock()
    staged = []
    peak = [0]

    def stage(i):
        with lock:
            staged.append(i)
            peak[0] = max(peak[0], len(staged))
        return i

    consumed = []
    for x in pipeline_map(range(50), stage, depth=2):
        with lock:
            staged.remove(x)  # delivered: no longer staged
        # let the worker run ahead as far as it can while we "compute"
        time.sleep(0.002)
        consumed.append(x)
    assert consumed == list(range(50))  # order preserved
    assert 1 <= peak[0] <= 2, f"staged-ahead peak {peak[0]} exceeds depth"


def test_pipeline_depth_zero_is_synchronous():
    """depth=0 must not spawn a worker: production interleaves strictly
    with consumption (the escape-hatch semantics)."""
    trace = []

    def gen():
        for i in range(4):
            trace.append(("produce", i))
            yield i

    for x in pipeline_map(gen(), lambda i: i, depth=0):
        trace.append(("consume", x))
    assert trace == [("produce", 0), ("consume", 0),
                     ("produce", 1), ("consume", 1),
                     ("produce", 2), ("consume", 2),
                     ("produce", 3), ("consume", 3)]


def test_pipeline_propagates_errors_and_stops():
    def gen():
        yield 1
        raise ValueError("upstream boom")

    it = pipeline_batches(gen(), depth=2)
    assert next(it) == 1
    with pytest.raises(ValueError, match="upstream boom"):
        next(it)


def test_pipeline_abandon_closes_upstream():
    """A consumer that stops early (LIMIT) must close the upstream
    generator instead of leaking the worker + staged batches."""
    closed = threading.Event()

    def gen():
        try:
            for i in range(1000):
                yield i
        finally:
            closed.set()

    it = pipeline_batches(gen(), depth=2)
    assert next(it) == 0
    it.close()
    assert closed.wait(timeout=5.0), "upstream generator never closed"


def test_effective_depth_resolution(session):
    """OOM-injection runs disable pipelining (deterministic injection
    points need a single thread issuing device ops); on the CPU backend
    the unset default resolves to serial (same-silicon overlap is pure
    contention) while an explicit depth always wins."""
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.plan.physical import ExecContext
    ctx = ExecContext()
    try:
        # unset on the CPU test backend: backend-aware default = serial
        assert effective_depth(ctx) == 0
        # explicitly set: honored verbatim
        ctx_set = ExecContext(TpuConf(
            {"spark.rapids.tpu.sql.pipeline.depth": 3}))
        assert effective_depth(ctx_set) == 3
        # OOM injection armed: forced serial even when explicitly set
        ctx_inj = ExecContext(ctx_set.conf.with_settings(**{
            "spark.rapids.tpu.test.injectRetryOOM": 1}))
        assert effective_depth(ctx_inj) == 0
    finally:
        # disarm: ExecContext arms the process-global OOM injector
        ExecContext(ctx.conf)


def test_stage_output_correct(session):
    """A fused filter + project stage over a fresh scan gives the rows
    the expressions define."""
    f = srt.functions
    df = session.create_dataframe(
        {"x": np.arange(100, dtype=np.int64)})
    rows = (df.filter(f.col("x") % 2 == 0)
              .select((f.col("x") * 10).alias("y")).collect())
    assert sorted(r[0] for r in rows) == [x * 10 for x in range(0, 100, 2)]

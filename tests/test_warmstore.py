"""Warm-start subsystem (runtime/warmstore.py): store persistence and
corruption tolerance, LRU bounds, export/import shipping, the compile
ledger's prewarm/store_hit taxonomy (a prewarm burst must NOT read as a
storm), initialize()'s same-conf reuse, prewarm budget bounds, the
/debug/warmstore render, the unwritable-dir degradations, and the
in-process restart differential over the real wire door (drain → ship →
simulated restart → prewarm → zero post_restart compiles)."""

import json
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.plan import bucketing, physical
from spark_rapids_tpu.runtime import warmstore
from spark_rapids_tpu.runtime.warmstore import WarmStore
from spark_rapids_tpu.server import SqlFrontDoor, WireClient
from spark_rapids_tpu.utils import recorder, telemetry


@pytest.fixture(autouse=True)
def _fresh():
    warmstore.reset_for_tests()
    recorder.reset_for_tests()
    telemetry.reset_for_tests()
    yield
    warmstore.reset_for_tests()
    recorder.reset_for_tests()
    telemetry.reset_for_tests()
    bucketing.reset_for_tests()


def _conf(tmp_path=None, **over):
    c = {"spark.rapids.tpu.warmstore.enabled": True,
         "spark.rapids.tpu.warmstore.dir":
             str(tmp_path) if tmp_path is not None else ""}
    c.update(over)
    return TpuConf(c)


def _ctr(name, label=""):
    series = telemetry.snapshot().get(name) or {}
    return sum(v for k, v in series.items() if label in k)


SPEC = {"table": "t", "ops": [
    {"op": "agg", "group": ["k"],
     "aggs": [["n", "count", "*"], ["s", "sum", ["col", "v"]]]},
    {"op": "sort", "keys": [["k", True]]}]}


def _shipped_entry(fp, hits=1, spec=SPEC):
    """A wire-shaped entry (what export_hot emits / import_shipped
    accepts) with a bogus program record: prewarm counts the statement
    even when no recorded program key matches the re-planned stages."""
    return {"fp": fp, "ladder": bucketing.ladder_signature(),
            "hits": hits, "spec": spec,
            "programs": {"bogus|" + fp: {"sig": {}, "bucket": "b"}}}


# ---------------------------------------------------------------------------
# Store: persistence, corruption, LRU, shipping
# ---------------------------------------------------------------------------

class TestStore:
    def test_roundtrip_persistence(self, tmp_path):
        conf = _conf(tmp_path)
        st = WarmStore(conf)
        st.note_statement("fpA", SPEC)
        st.note_program("stage|p1", "fpA", {"arrays": []}, 1024)
        st.flush()
        st2 = WarmStore(conf)
        snap = st2.snapshot()
        assert snap["entries"] == 1
        top = snap["top"][0]
        assert top["warm"] and top["has_spec"] and top["programs"] == 1
        # a reloaded manifest marks its fingerprints store-known: the
        # next compile is a disk deserialization, not a storm
        assert recorder.compile_ledger().note(0.1, "fpA") == "store_hit"

    def test_warm_hit_counted_on_first_touch(self, tmp_path):
        conf = _conf(tmp_path)
        st = WarmStore(conf)
        st.note_statement("fpA", SPEC)
        st.flush()
        assert st.misses == 1 and st.hits == 0
        st2 = WarmStore(conf)
        st2.note_statement("fpA", SPEC)
        st2.note_statement("fpA", SPEC)  # second touch: no double count
        assert st2.hits == 1 and st2.misses == 0

    def test_corrupt_manifest_starts_empty(self, tmp_path):
        (tmp_path / "manifest.json").write_text("{ not json !!")
        st = WarmStore(_conf(tmp_path))
        assert st.corrupt == 1
        assert st.snapshot()["entries"] == 0
        assert _ctr("warmstore_corrupt_total") == 1.0
        # the store still works after the corrupt load
        st.note_statement("fpA", SPEC)
        st.flush()
        assert WarmStore(_conf(tmp_path)).snapshot()["entries"] == 1

    def test_one_bad_entry_drops_rest_load(self, tmp_path):
        good = {"key": "k1", "fp": "fpA", "hits": 3, "programs": {}}
        (tmp_path / "manifest.json").write_text(json.dumps(
            {"version": 1, "entries": [good, "not-a-dict", 42]}))
        st = WarmStore(_conf(tmp_path))
        assert st.snapshot()["entries"] == 1
        assert st.corrupt == 2

    def test_lru_entry_bound(self, tmp_path):
        conf = _conf(tmp_path, **{
            "spark.rapids.tpu.warmstore.maxEntries": 2})
        st = WarmStore(conf)
        for i in range(5):
            st.note_statement(f"fp{i}", SPEC)
        snap = st.snapshot()
        assert snap["entries"] == 2
        assert st.evictions == 3
        assert _ctr("warmstore_evictions_total") == 3.0
        # most-recent survive
        fps = {e["fingerprint"] for e in snap["top"]}
        assert fps == {"fp3", "fp4"}

    def test_lru_byte_bound(self, tmp_path):
        conf = _conf(tmp_path, **{
            "spark.rapids.tpu.warmstore.maxBytes": 4096})
        st = WarmStore(conf)
        for i in range(40):
            st.note_statement(f"fp{i}", SPEC)
        assert st.approx_bytes() <= 4096
        assert st.snapshot()["entries"] >= 1  # never evicts to zero
        assert st.evictions > 0

    def test_export_import_ship(self, tmp_path):
        a = WarmStore(_conf(tmp_path / "a"))
        for i in range(4):
            fp = f"fp{i}"
            a.note_statement(fp, SPEC)
            for _ in range(i):  # fp3 hottest
                a.note_statement(fp)
        payload = a.export_hot(2)
        assert [e["fp"] for e in payload] == ["fp3", "fp2"]
        b = WarmStore(_conf(tmp_path / "b"))
        assert b.import_shipped(payload) == 2
        assert b.shipped_in == 2
        snap = b.snapshot()
        assert snap["entries"] == 2
        assert all(e["warm"] for e in snap["top"])
        assert _ctr("warmstore_shipped_total", "received") == 2.0
        # shipped fingerprints classify store_hit, and survive a flush
        assert recorder.compile_ledger().note(0.1, "fp3") == "store_hit"
        b.flush()
        assert WarmStore(_conf(tmp_path / "b")).snapshot()["entries"] == 2

    def test_import_rekeys_to_local_topology(self, tmp_path):
        b = WarmStore(_conf(tmp_path))
        ent = _shipped_entry("fpX")
        ent["ladder"] = "g9:a9:s9"  # a sibling on a different ladder
        assert b.import_shipped([ent]) == 1
        key = b.snapshot()["top"][0]["key"]
        assert key == warmstore._entry_key("fpX", "g9:a9:s9",
                                          b._topology())

    def test_unwritable_dir_degrades_in_memory(self, tmp_path):
        blocker = tmp_path / "file"
        blocker.write_text("x")
        st = WarmStore(_conf(blocker / "sub"))  # mkdir under a file
        assert st._dir is None
        assert _ctr("warmstore_errors_total", "store_dir") == 1.0
        st.note_statement("fpA", SPEC)  # in-memory still serves
        st.flush()  # and flushing nowhere never raises
        assert st.snapshot()["entries"] == 1

    def test_setup_jax_cache_unwritable_counts(self, tmp_path, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        blocker = tmp_path / "file"
        blocker.write_text("x")
        conf = TpuConf({"spark.rapids.tpu.xla.cacheDir":
                        str(blocker / "sub")})
        assert warmstore.setup_jax_cache(conf) is False
        assert _ctr("warmstore_errors_total", "cache_dir") == 1.0

    def test_env_places_the_cache_and_code_sets_none(self, tmp_path,
                                                     monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself, so
        setup_jax_cache leaves jax_compilation_cache_dir alone, a
        disagreeing xla.cacheDir is ignored, and the index follows."""
        import jax
        env_dir = str(tmp_path / "from_env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        conf = TpuConf({"spark.rapids.tpu.xla.cacheDir":
                        str(tmp_path / "from_conf")})
        before = jax.config.jax_compilation_cache_dir
        assert warmstore.setup_jax_cache(conf) is True
        assert jax.config.jax_compilation_cache_dir == before
        assert warmstore.xla_cache_dir(conf) == env_dir
        assert warmstore.store_dir(conf) == env_dir + "/warmstore"
        assert not (tmp_path / "from_conf").exists()

    def test_default_cache_is_one_path_inside_the_checkout(
            self, tmp_path, monkeypatch):
        import os
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.chdir(tmp_path)  # never the working directory
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert warmstore.xla_cache_dir(TpuConf()) == \
            os.path.join(repo, ".cache", "xla")
        assert warmstore.store_dir(TpuConf()) == \
            os.path.join(repo, ".cache", "xla", "warmstore")
        # no cache, no relative place for the index: in-memory
        off = TpuConf({"spark.rapids.tpu.xla.cacheDir": ""})
        assert warmstore.xla_cache_dir(off) is None
        assert warmstore.store_dir(off) is None


# ---------------------------------------------------------------------------
# Singleton lifecycle: initialize() reuse + simulate_restart()
# ---------------------------------------------------------------------------

class TestLifecycle:
    def test_initialize_reuses_same_conf(self, tmp_path):
        conf = _conf(tmp_path)
        st = warmstore.initialize(conf)
        st.note_statement("fpA", SPEC)
        # a second door in the same process MUST share the live index
        assert warmstore.initialize(conf) is st
        assert st.snapshot()["entries"] == 1

    def test_initialize_swaps_on_conf_change(self, tmp_path):
        st = warmstore.initialize(_conf(tmp_path / "a"))
        st.note_statement("fpA", SPEC)
        st2 = warmstore.initialize(_conf(tmp_path / "b"))
        assert st2 is not st
        # the displaced store flushed on the way out
        assert json.load(open(tmp_path / "a" / "manifest.json"))[
            "entries"]

    def test_initialize_disabled_returns_none(self, tmp_path):
        assert warmstore.initialize(_conf(
            tmp_path, **{"spark.rapids.tpu.warmstore.enabled": False})) \
            is None
        assert warmstore.store() is None

    def test_simulate_restart_reloads_warm(self, tmp_path):
        conf = _conf(tmp_path)
        st = warmstore.initialize(conf)
        st.import_shipped([_shipped_entry("fpA", hits=5)])
        st.note_statement("fpB", SPEC)
        st2 = warmstore.simulate_restart(conf)
        assert st2 is not st and warmstore.store() is st2
        snap = st2.snapshot()
        assert snap["entries"] == 2
        assert all(e["warm"] for e in snap["top"])
        # untouched this "process": both are prewarm candidates (fpB
        # has no programs recorded, so only fpA qualifies)
        cands = st2.prewarm_candidates()
        assert [e["fp"] for e in cands] == ["fpA"]
        assert recorder.compile_ledger().note(0.1, "fpB") == "store_hit"


# ---------------------------------------------------------------------------
# Ledger taxonomy: prewarm / store_hit vs the storm detector
# ---------------------------------------------------------------------------

class TestLedgerTaxonomy:
    def test_prewarm_scope_classifies_and_never_storms(self):
        led = recorder.compile_ledger()
        for i in range(recorder.STORM_THRESHOLD + 4):
            with recorder.compile_prewarm_scope(f"fp{i}"):
                # the listener sees prewarm compiles with NO live
                # fingerprint; the scope carries it
                assert led.note(0.05, None) == "prewarm"
        assert not led.storming
        assert _ctr("compiles_by_trigger_total", "prewarm") \
            == recorder.STORM_THRESHOLD + 4

    def test_store_hit_burst_never_storms(self):
        led = recorder.compile_ledger()
        fps = [f"fp{i}" for i in range(recorder.STORM_THRESHOLD + 4)]
        recorder.compile_store_known(fps)
        for fp in fps:
            assert led.note(0.05, fp) == "store_hit"
        assert not led.storming

    def test_store_hit_wins_over_primed(self):
        led = recorder.compile_ledger()
        recorder.compile_prime(["fpA", "fpB"])
        recorder.compile_store_known(["fpA"])
        assert led.note(0.1, "fpA") == "store_hit"
        assert led.note(0.1, "fpB") == "post_restart"

    def test_prewarm_consumes_warm_markers(self):
        """After a prewarm compiled fpA, its later live compiles (new
        shapes) must classify honestly — not replay store_hit."""
        led = recorder.compile_ledger()
        recorder.compile_prime(["fpA"])
        recorder.compile_store_known(["fpA"])
        with recorder.compile_prewarm_scope("fpA"):
            assert led.note(0.05, None) == "prewarm"
        assert led.note(0.1, "fpA") == "shape_change"


# ---------------------------------------------------------------------------
# Prewarm pass: ordering, budget bounds
# ---------------------------------------------------------------------------

class TestPrewarm:
    def _arm(self, tmp_path, n=4, **over):
        conf = _conf(tmp_path, **over)
        st = warmstore.initialize(conf)
        st.import_shipped([_shipped_entry(f"fp{i}", hits=i)
                           for i in range(n)])
        return conf, st

    def _door_ctx(self, session):
        from spark_rapids_tpu.server.prepared import PreparedCache
        t = pa.table({"k": np.arange(100, dtype="int64") % 7,
                      "v": np.linspace(0.0, 1.0, 100)})
        tables = {"t": lambda: session.create_dataframe(t)}
        return PreparedCache(), tables

    def test_candidates_hottest_first(self, tmp_path):
        _, st = self._arm(tmp_path)
        assert [e["fp"] for e in st.prewarm_candidates()] \
            == ["fp3", "fp2", "fp1", "fp0"]

    def test_max_statements_bounds_pass(self, session, tmp_path):
        conf, st = self._arm(tmp_path, **{
            "spark.rapids.tpu.warmstore.prewarm.maxStatements": 2})
        prepared, tables = self._door_ctx(session)
        out = warmstore.prewarm(session, prepared, tables, conf)
        assert out["prewarmed"] == 2
        assert out["skipped"] == 2
        assert st.prewarmed == 2
        assert _ctr("warmstore_prewarmed_total") == 2.0

    def test_zero_budget_compiles_nothing(self, session, tmp_path):
        conf, st = self._arm(tmp_path, **{
            "spark.rapids.tpu.warmstore.prewarm.budgetS": 0.0})
        prepared, tables = self._door_ctx(session)
        out = warmstore.prewarm(session, prepared, tables, conf)
        assert out["prewarmed"] == 0
        assert out["skipped"] == 4

    def test_unknown_table_skips_not_errors(self, session, tmp_path):
        conf, st = self._arm(tmp_path, n=1)
        prepared, tables = self._door_ctx(session)
        out = warmstore.prewarm(session, prepared, {}, conf)
        assert out["errors"] == 0
        assert out["skipped"] == 1
        assert _ctr("warmstore_errors_total", "prewarm") == 0.0

    def test_stop_event_short_circuits(self, session, tmp_path):
        import threading
        conf, st = self._arm(tmp_path)
        prepared, tables = self._door_ctx(session)
        stop = threading.Event()
        stop.set()
        out = warmstore.prewarm(session, prepared, tables, conf,
                                stop=stop)
        assert out["prewarmed"] == 0


# ---------------------------------------------------------------------------
# /debug/warmstore render
# ---------------------------------------------------------------------------

class TestDebugRender:
    def test_disabled_renders_placeholder(self):
        from spark_rapids_tpu.server.ops import render_debug_warmstore
        assert render_debug_warmstore() == "warmstore: disabled\n"

    def test_render_shows_entries_and_counters(self, tmp_path):
        from spark_rapids_tpu.server.ops import render_debug_warmstore
        st = warmstore.initialize(_conf(tmp_path))
        st.note_statement("fpAAAA", SPEC)
        st.import_shipped([_shipped_entry("fpBBBB", hits=9)])
        text = render_debug_warmstore()
        assert "2/256 entries" in text
        assert "shipped_in=1" in text
        assert "fpAAAA" in text and "fpBBBB" in text
        assert "FINGERPRINT" in text


# ---------------------------------------------------------------------------
# The in-process restart differential over the real wire door: the
# loadgen --restart-probe acceptance, scaled down to a unit test.
# ---------------------------------------------------------------------------

class TestRestartDifferential:
    N = 4_000

    def _mk_door(self, session, tmp_path, tables):
        door = SqlFrontDoor(session, settings={
            "spark.rapids.tpu.warmstore.enabled": True,
            "spark.rapids.tpu.warmstore.dir": str(tmp_path),
        }).start()
        for name, f in tables.items():
            door.register_table(name, f)
        return door

    def _exec(self, door, spec):
        with WireClient("127.0.0.1", door.port) as c:
            h = c.prepare(spec)
            return sorted(c.execute(h["statement_id"]).rows())

    def test_drain_ships_then_restart_prewarms(self, session, tmp_path):
        rng = np.random.default_rng(20260807)
        t = pa.table({
            "k": rng.integers(0, 11, self.N).astype("int64"),
            "v": rng.random(self.N) * 100.0})
        tables = {"t": lambda: session.create_dataframe(t)}
        spec = {"table": "t", "ops": [
            {"op": "filter", "expr": [">", ["col", "v"], ["lit", 3.0]]},
            {"op": "agg", "group": ["k"],
             "aggs": [["n", "count", "*"], ["s", "sum", ["col", "v"]]]},
            {"op": "sort", "keys": [["k", True]]}]}

        d1 = self._mk_door(session, tmp_path, tables)
        sibling = None
        try:
            want = self._exec(d1, spec)
            assert len(want) == 11
            st = warmstore.store()
            assert st is not None
            snap = st.snapshot()
            assert snap["entries"] >= 1
            assert snap["top"][0]["programs"] >= 1, \
                "execute must record stage program signatures"

            # drain ships the hot entries to the GOAWAY sibling (same
            # store conf: doors in one process share the live index)
            sibling = self._mk_door(session, tmp_path, tables)
            report = d1.drain(deadline_s=2.0,
                              siblings=[("127.0.0.1", sibling.port)],
                              linger_s=0.0)
            assert report["warm_entries_shipped"] >= 1
            sib_store = warmstore.store()
            assert sib_store.shipped_in >= 1
        finally:
            d1.close()
            if sibling is not None:
                sibling.close()

        # --- simulated process restart -------------------------------
        conf = _conf(tmp_path)
        old_fps = warmstore.store().fingerprints()
        assert old_fps
        evicted = physical.clear_program_cache()
        assert evicted, "the pre-restart door must have compiled"
        recorder.reset_for_tests()
        telemetry.reset_for_tests()
        recorder.compile_prime(old_fps)  # a cold path would storm
        warmstore.simulate_restart(conf)

        d2 = self._mk_door(session, tmp_path, tables)
        try:
            deadline = time.monotonic() + 30.0  # span-api-ok (test poll deadline)
            while time.monotonic() < deadline:  # span-api-ok (test poll deadline)
                if warmstore.snapshot()["prewarmed"] >= 1:
                    break
                time.sleep(0.1)
            snap = warmstore.snapshot()
            assert snap["prewarmed"] >= 1, snap
            assert physical.program_cache_size() >= 1, \
                "prewarm must install AOT programs before traffic"
            assert _ctr("compiles_by_trigger_total", "prewarm") >= 1.0

            got = self._exec(d2, spec)
            assert got == want
            # THE acceptance: nothing classified post_restart — the
            # store/prewarm path covered every fingerprint it knew
            assert _ctr("compiles_by_trigger_total",
                        "post_restart") == 0.0
        finally:
            d2.close()

"""tools/srtlint — the unified AST static analysis engine.

Covers, per pass: detection on fixture snippets (including the
defect classes the retired regex scanners provably missed), reasoned
suppression, and the baseline workflow; plus the engine surfaces
(CLI, JSON, explain, mtime-keyed cache) and the acceptance gates:
the real tree is clean and a full run fits the collection wall budget.
"""

import json
import os
import time

import pytest

from tools.srtlint import engine
from tools.srtlint.engine import run as lint_run

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree(tmp_path, files):
    """Write {relpath: source} under a fixture spark_rapids_tpu/."""
    for rel, src in files.items():
        p = tmp_path / "spark_rapids_tpu" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return str(tmp_path)


def _lint(tmp_path, files, rules):
    return lint_run(_tree(tmp_path, files),
                    roots=("spark_rapids_tpu",), rules=rules)


# ---------------------------------------------------------------------------
# ported passes: the regex scanners' false-negative classes are caught
# ---------------------------------------------------------------------------

class TestBlockingFetch:
    def test_aliased_device_get_regex_false_negative(self, tmp_path):
        """`from jax import device_get as dg` dodged the old
        `jax.device_get(` line regex entirely."""
        report = _lint(tmp_path, {"plan/bad.py": (
            "from jax import device_get as dg\n"
            "def f(x):\n"
            "    return dg(x)\n")}, ["blocking-fetch"])
        assert [f.line for f in report.failing] == [3]
        assert "choke point" in report.failing[0].message

    def test_multiline_asarray_and_suppression(self, tmp_path):
        """A call spanning lines (regex saw only line 1) + a reasoned
        legacy marker anywhere on the statement suppresses."""
        report = _lint(tmp_path, {"ops/bad.py": (
            "import numpy as np\n"
            "def f(col):\n"
            "    return np.asarray(\n"
            "        col.data)\n"
            "def g(col):\n"
            "    return np.asarray(\n"
            "        col.codes)  # choke-point-ok (host column; no device buffer)\n")},
            ["blocking-fetch"])
        assert [f.line for f in report.failing] == [3]
        assert len(report.suppressed) == 1

    def test_outside_operator_layer_ignored(self, tmp_path):
        report = _lint(tmp_path, {"io/x.py": (
            "import jax\n"
            "def f(x):\n"
            "    return jax.device_get(x)\n")}, ["blocking-fetch"])
        assert report.failing == []

    def test_region_fusible_raw_sync_detected(self, tmp_path):
        """A raw fetch/fetch_scalars inside a ``region_fusible = True``
        operator body breaks the one-prologue-fetch-per-region
        contract; the same call in a non-fusible class is fine."""
        report = _lint(tmp_path, {"plan/bad.py": (
            "from spark_rapids_tpu.utils.metrics import fetch, fetch_scalars\n"
            "class FooExec:\n"
            "    region_fusible = True\n"
            "    def execute(self, ctx):\n"
            "        n = fetch_scalars(ctx.counts)[0]\n"
            "        return fetch(ctx.batch)\n"
            "class BarExec:\n"
            "    region_fusible = False\n"
            "    def execute(self, ctx):\n"
            "        return fetch(ctx.batch)\n")}, ["blocking-fetch"])
        assert sorted(f.line for f in report.failing) == [5, 6]
        assert all("region prologue" in f.message for f in report.failing)

    def test_region_fusible_fusion_ok_suppresses(self, tmp_path):
        """``# fusion-ok (<why>)`` exempts a sync that genuinely cannot
        ride the prologue; the prologue APIs themselves never flag."""
        report = _lint(tmp_path, {"plan/ok.py": (
            "from spark_rapids_tpu.utils.metrics import (\n"
            "    fetch, region_scalars, stage_scalars)\n"
            "class FooExec:\n"
            "    region_fusible = True\n"
            "    def execute(self, ctx):\n"
            "        stage_scalars('k', ctx.counts)\n"
            "        n = region_scalars(ctx.counts)[0]\n"
            "        tail = fetch(ctx.tail)  # fusion-ok (end-of-stream tail: one batched fetch by construction)\n"
            "        return n, tail\n")}, ["blocking-fetch"])
        assert report.failing == []
        assert len(report.suppressed) == 1


class TestSpanTiming:
    def test_aliased_clock_import(self, tmp_path):
        """`from time import perf_counter` was invisible to the
        `time.perf_counter(` regex."""
        report = _lint(tmp_path, {"parallel/bad.py": (
            "from time import perf_counter as pc\n"
            "t0 = pc()\n")}, ["span-timing"])
        assert [f.line for f in report.failing] == [2]


class TestCtxThreads:
    def test_evidence_beyond_regex_window(self, tmp_path):
        """copy_context evidence 5+ lines from the creation site was a
        false POSITIVE for the ±3-line regex window; the AST pass
        scopes evidence to the enclosing function."""
        src = (
            "import contextvars, threading\n"
            "def spawn(fn):\n"
            "    cctx = contextvars.copy_context()\n"
            "    a = 1\n"
            "    b = 2\n"
            "    c = 3\n"
            "    d = 4\n"
            "    th = threading.Thread(target=lambda: cctx.run(fn))\n"
            "    th.start()\n")
        report = _lint(tmp_path, {"runtime/pool.py": src},
                       ["ctx-threads"])
        assert report.failing == []

    def test_detect_and_reasoned_suppress(self, tmp_path):
        report = _lint(tmp_path, {"runtime/bad.py": (
            "import threading\n"
            "def spawn(fn):\n"
            "    threading.Thread(target=fn).start()\n"
            "def ok(fn):\n"
            "    threading.Thread(target=fn).start()  # ctx-ok (process-lifetime control plane)\n")},
            ["ctx-threads"])
        assert [f.line for f in report.failing] == [3]
        assert len(report.suppressed) == 1


class TestCacheKeys:
    def test_aliased_constructor_and_multiline_literal(self, tmp_path):
        """Both regex false-negative classes: an aliased CacheKey
        import and a literal key split across lines."""
        report = _lint(tmp_path, {"plan/bad.py": (
            "from ..cache.keys import CacheKey as CK\n"
            "def f(cache, schema):\n"
            "    k = CK('scan', (), None, None)\n"
            "    return cache.lookup_scan(\n"
            "        ('adhoc',\n"
            "         'tuple'), schema)\n")}, ["cache-keys"])
        assert sorted(f.line for f in report.failing) == [3, 4]

    def test_keys_module_itself_exempt(self, tmp_path):
        report = _lint(tmp_path, {"cache/keys.py": (
            "class CacheKey:\n"
            "    pass\n"
            "def scan_key():\n"
            "    return CacheKey()\n")}, ["cache-keys"])
        assert report.failing == []


class TestFaultPaths:
    def test_multiline_except_sleep_pair(self, tmp_path):
        """A sleep 10 lines into the handler suite: past the regex
        scanner's 8-line window, inside the AST handler scope."""
        filler = "".join(f"        x{i} = {i}\n" for i in range(10))
        report = _lint(tmp_path, {"io/bad.py": (
            "import time\n"
            "def r():\n"
            "    try:\n"
            "        return g()\n"
            "    except OSError:\n"
            + filler +
            "        time.sleep(0.1)\n")}, ["fault-paths"])
        assert len(report.failing) == 1
        assert "ad-hoc retry" in report.failing[0].message
        assert report.failing[0].line == 16

    def test_swallowed_fault_marker_on_pass_line(self, tmp_path):
        report = _lint(tmp_path, {"io/x.py": (
            "def f():\n"
            "    try:\n"
            "        g()\n"
            "    except Exception:\n"
            "        pass  # fault-ok (best-effort hint)\n"
            "    try:\n"
            "        g()\n"
            "    except BaseException:\n"
            "        pass\n")}, ["fault-paths"])
        assert [f.line for f in report.failing] == [8]
        assert len(report.suppressed) == 1


# ---------------------------------------------------------------------------
# new passes
# ---------------------------------------------------------------------------

class TestReleasePaths:
    def test_leaked_handle_detected(self, tmp_path):
        report = _lint(tmp_path, {"plan/bad.py": (
            "def f(catalog, b):\n"
            "    h = catalog.register(b)\n"
            "    h.get()\n")}, ["release-paths"])
        assert len(report.failing) == 1
        assert "never released" in report.failing[0].message

    def test_straight_line_release_flagged(self, tmp_path):
        report = _lint(tmp_path, {"plan/bad.py": (
            "def f(catalog, b):\n"
            "    h = catalog.register(b)\n"
            "    work(h)\n"
            "    h.close()\n")}, ["release-paths"])
        assert len(report.failing) == 1
        assert "straight-line" in report.failing[0].message

    def test_finally_release_clean(self, tmp_path):
        report = _lint(tmp_path, {"plan/ok.py": (
            "def f(catalog, b):\n"
            "    h = catalog.register(b)\n"
            "    try:\n"
            "        work(h)\n"
            "    finally:\n"
            "        h.close()\n")}, ["release-paths"])
        assert report.failing == []

    def test_exit_edge_between_acquire_and_finally(self, tmp_path):
        """CFG-lite: a return between acquisition and its protecting
        try/finally is a leak edge."""
        report = _lint(tmp_path, {"plan/bad.py": (
            "def f(catalog, b, flag):\n"
            "    h = catalog.register(b)\n"
            "    if flag:\n"
            "        return None\n"
            "    try:\n"
            "        return work(h)\n"
            "    finally:\n"
            "        h.close()\n")}, ["release-paths"])
        assert len(report.failing) == 1
        assert report.failing[0].line == 4
        assert "leaks" in report.failing[0].message

    def test_escape_and_with_are_clean(self, tmp_path):
        report = _lint(tmp_path, {"plan/ok.py": (
            "def f(catalog, b, out):\n"
            "    h = catalog.register(b)\n"
            "    out.append(h)\n"
            "def g(sem):\n"
            "    with sem.acquire():\n"
            "        pass\n"
            "def r(cache, key):\n"
            "    hit = cache.lookup_broadcast(key)\n"
            "    return hit\n")}, ["release-paths"])
        assert report.failing == []

    def test_paired_void_quota(self, tmp_path):
        report = _lint(tmp_path, {"server/bad.py": (
            "def f(quotas, tenant):\n"
            "    quotas.acquire(tenant)\n"
            "    work()\n"
            "    quotas.release(tenant)\n"
            "def ok(quotas, tenant):\n"
            "    quotas.acquire(tenant)\n"
            "    try:\n"
            "        work()\n"
            "    finally:\n"
            "        quotas.release(tenant)\n")}, ["release-paths"])
        assert [f.line for f in report.failing] == [2]
        assert "finally" in report.failing[0].message


class TestLockDiscipline:
    def test_blocking_under_lock(self, tmp_path):
        report = _lint(tmp_path, {"service/bad.py": (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self, sock):\n"
            "        with self._lock:\n"
            "            sock.recv(4096)\n")}, ["lock-discipline"])
        assert len(report.failing) == 1
        assert "sock.recv" in report.failing[0].message

    def test_cv_self_wait_not_flagged(self, tmp_path):
        report = _lint(tmp_path, {"service/ok.py": (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._cv = threading.Condition()\n"
            "    def f(self):\n"
            "        with self._cv:\n"
            "            self._cv.wait()\n")}, ["lock-discipline"])
        assert report.failing == []

    def test_blocking_through_helper(self, tmp_path):
        """Interprocedural summary: the blocking call hides one level
        down in a same-module helper."""
        report = _lint(tmp_path, {"service/bad.py": (
            "import threading\n"
            "def _pull(sock):\n"
            "    return sock.recv(4096)\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self, sock):\n"
            "        with self._lock:\n"
            "            return _pull(sock)\n")}, ["lock-discipline"])
        assert len(report.failing) == 1
        assert "reaches blocking" in report.failing[0].message

    def test_lock_order_cycle(self, tmp_path):
        report = _lint(tmp_path, {"cache/bad.py": (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def ab(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
            "    def ba(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                pass\n")}, ["lock-discipline"])
        cyc = [f for f in report.failing if "cycle" in f.message]
        assert len(cyc) == 2  # one per participating edge
        assert "one global order" in cyc[0].message

    def test_consistent_order_no_cycle(self, tmp_path):
        report = _lint(tmp_path, {"cache/ok.py": (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def ab(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
            "    def ab2(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n")}, ["lock-discipline"])
        assert report.failing == []


_CONF_FIXTURE = {
    "config.py": (
        "def register(key, default, doc, **kw):\n"
        "    return key\n"
        "A = register('spark.rapids.tpu.a', 1, 'used and documented')\n"
        "B = register('spark.rapids.tpu.b', 1, 'internal',\n"
        "             internal=True)\n"
        "ORPHAN = register('spark.rapids.tpu.orphan', 1, 'dead')\n"),
    "user.py": (
        "from .config import B\n"
        "def f(conf, tier):\n"
        "    x = conf['spark.rapids.tpu.a']\n"
        "    y = conf['spark.rapids.tpu.nope']\n"
        "    z = conf[f'spark.rapids.tpu.{tier}.enabled']\n"
        "    return x, y, z, B\n"),
}


class TestConfRegistry:
    def _run(self, tmp_path, docs: str):
        root = _tree(tmp_path, _CONF_FIXTURE)
        os.makedirs(os.path.join(root, "docs"), exist_ok=True)
        with open(os.path.join(root, "docs", "configs.md"), "w") as f:
            f.write(docs)
        return lint_run(root, roots=("spark_rapids_tpu",),
                        rules=["conf-registry"])

    def test_unknown_dynamic_orphan_and_docs(self, tmp_path):
        report = self._run(
            tmp_path,
            "| spark.rapids.tpu.a | 1 | doc |\n"
            "| spark.rapids.tpu.orphan | 1 | doc |\n"
            "| spark.rapids.tpu.stale | 1 | doc |\n")
        msgs = sorted(f.message for f in report.failing)
        assert any("'spark.rapids.tpu.nope' is not registered" in m
                   for m in msgs)
        assert any("f-string" in m for m in msgs)
        assert any("'spark.rapids.tpu.orphan' is orphaned" in m
                   for m in msgs)
        assert any("no longer registered" in m for m in msgs)
        # the internal key B needs no docs entry and is referenced
        assert not any("'spark.rapids.tpu.b'" in m for m in msgs)

    def test_missing_doc_entry(self, tmp_path):
        report = self._run(tmp_path,
                           "| spark.rapids.tpu.orphan | 1 | doc |\n")
        assert any("missing from docs/configs.md" in f.message
                   and "'spark.rapids.tpu.a'" in f.message
                   for f in report.failing)


# ---------------------------------------------------------------------------
# engine: suppression hygiene, baseline workflow, cache, CLI
# ---------------------------------------------------------------------------

class TestEngine:
    def test_srtlint_ignore_syntax_and_reason_required(self, tmp_path):
        report = _lint(tmp_path, {"plan/x.py": (
            "import jax\n"
            "a = jax.device_get(1)  # srtlint: ignore[blocking-fetch] (test seed, not a device value)\n"
            "b = jax.device_get(2)  # srtlint: ignore[blocking-fetch]\n")},
            ["blocking-fetch"])
        assert [f.line for f in report.failing] == [3]
        assert "no reason" in report.failing[0].message
        assert [f.line for f in report.suppressed] == [2]
        assert "test seed" in report.suppressed[0].suppress_reason

    def test_baseline_workflow(self, tmp_path):
        files = {"plan/bad.py": ("import jax\n"
                                 "a = jax.device_get(1)\n")}
        root = _tree(tmp_path, files)
        bl = str(tmp_path / "baseline.json")
        report = lint_run(root, roots=("spark_rapids_tpu",),
                          rules=["blocking-fetch"], baseline_path=bl)
        assert len(report.failing) == 1
        engine.write_baseline(report.failing, bl)
        again = lint_run(root, roots=("spark_rapids_tpu",),
                         rules=["blocking-fetch"], baseline_path=bl)
        assert again.failing == []
        assert len(again.baselined) == 1
        # line drift does not invalidate the baseline entry
        files = {"plan/bad.py": ("import jax\n# pushed down\n"
                                 "a = jax.device_get(1)\n")}
        root = _tree(tmp_path, files)
        moved = lint_run(root, roots=("spark_rapids_tpu",),
                         rules=["blocking-fetch"], baseline_path=bl)
        assert moved.failing == []
        assert len(moved.baselined) == 1

    def test_cli_exit_codes_and_json(self, tmp_path, capsys):
        root = _tree(tmp_path, {"plan/bad.py": (
            "import jax\na = jax.device_get(1)\n")})
        assert engine.main(["--repo", root, "--json"]) == 1
        out = json.loads(capsys.readouterr().out)
        assert out["counts"]["failing"] == 1
        root2 = _tree(tmp_path / "clean", {"plan/ok.py": "x = 1\n"})
        assert engine.main(["--repo", root2]) == 0
        assert engine.main(["--explain", "lock-discipline"]) == 0
        assert "lock-acquisition graph" in capsys.readouterr().out
        assert engine.main(["--explain", "nope"]) == 2

    def test_explain_covers_all_thirteen_rules(self):
        rules = engine.available_rules()
        assert rules == ["blocking-fetch", "span-timing", "ctx-threads",
                         "cache-keys", "fault-paths", "release-paths",
                         "lock-discipline", "shutdown-paths",
                         "shared-state-races", "typestate",
                         "protocol-conformance", "metrics-registry",
                         "conf-registry"]
        for r in rules:
            assert r in engine.explain_rule(r)

    def test_parse_error_is_a_finding(self, tmp_path):
        report = _lint(tmp_path, {"plan/broken.py": "def f(:\n"},
                       ["blocking-fetch"])
        assert [f.rule for f in report.failing] == ["parse-error"]


# ---------------------------------------------------------------------------
# PR 12 passes: races, typestate, protocol conformance
# ---------------------------------------------------------------------------

# the seeded unguarded-counter race: one accept loop spawning handler
# threads in a while loop (a MULTI-instance root), both bumping a
# counter the snapshot reads — no lock anywhere
_RACE_BAD = (
    "import threading\n"
    "class Door:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self.queries_total = 0\n"
    "    def start(self):\n"
    "        self._th = threading.Thread(target=self._accept_loop)\n"
    "        self._th.start()\n"
    "    def _accept_loop(self):\n"
    "        while True:\n"
    "            th = threading.Thread(target=self._handle)\n"
    "            th.start()\n"
    "    def _handle(self):\n"
    "        self.queries_total += 1\n"
    "    def close(self):\n"
    "        self._th.join(timeout=2.0)\n")


class TestSharedStateRaces:
    def test_unguarded_counter_across_handler_threads(self, tmp_path):
        report = _lint(tmp_path, {"server/bad.py": _RACE_BAD},
                       ["shared-state-races"])
        assert len(report.failing) == 1
        f = report.failing[0]
        assert "queries_total" in f.message and f.line == 14
        assert "[xN]" in f.message  # the multi-instance handler root

    def test_lock_guarded_counter_clean(self, tmp_path):
        src = _RACE_BAD.replace(
            "        self.queries_total += 1\n",
            "        with self._lock:\n"
            "            self.queries_total += 1\n")
        report = _lint(tmp_path, {"server/ok.py": src},
                       ["shared-state-races"])
        # the write is guarded; no OTHER access exists to pair with it
        assert report.failing == []

    def test_guarded_write_vs_bare_read_flagged_at_read(self, tmp_path):
        src = _RACE_BAD.replace(
            "        self.queries_total += 1\n",
            "        with self._lock:\n"
            "            self.queries_total += 1\n").replace(
            "    def close(self):\n",
            "    def snapshot(self):\n"
            "        return self.queries_total\n"
            "    def close(self):\n")
        report = _lint(tmp_path, {"server/bad.py": src},
                       ["shared-state-races"])
        assert len(report.failing) == 1
        assert report.failing[0].line == 17  # the bare read site

    def test_immutable_after_publish_and_single_writer_clean(
            self, tmp_path):
        report = _lint(tmp_path, {"server/ok.py": (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self.addr = ('h', 1)\n"       # init-only write
            "        self.count = 0\n"
            "    def start(self):\n"
            "        self._th = threading.Thread(target=self._loop)\n"
            "        self._th.start()\n"
            "    def _loop(self):\n"
            "        self.count += 1\n"            # single-writer root
            "    def peer(self):\n"
            "        return self.addr\n"
            "    def close(self):\n"
            "        self._th.join(timeout=2.0)\n")},
            ["shared-state-races"])
        assert report.failing == []

    def test_reasoned_suppression(self, tmp_path):
        src = _RACE_BAD.replace(
            "        self.queries_total += 1\n",
            "        self.queries_total += 1  # srtlint: ignore[shared-state-races] (GIL-atomic telemetry bump; a lost update skews a counter, never correctness)\n")
        report = _lint(tmp_path, {"server/ok.py": src},
                       ["shared-state-races"])
        assert report.failing == []
        assert len(report.suppressed) == 1
        assert "telemetry" in report.suppressed[0].suppress_reason

    def test_regression_endpoint_counter_guards(self, tmp_path):
        """PR 12 true positive: the front door's lifetime counters were
        bumped by N connection handlers with no lock.  Un-guarding the
        REAL endpoint.py must re-fire the pass — the fix cannot
        silently regress."""
        real = open(os.path.join(
            REPO, "spark_rapids_tpu", "server", "endpoint.py")).read()
        bad = real.replace(
            "                with self._lock:\n"
            "                    self.streamed_bytes += n\n",
            "                self.streamed_bytes += n\n")
        assert bad != real  # the guarded shape exists to revert
        report = _lint(tmp_path, {"server/endpoint.py": bad},
                       ["shared-state-races"])
        assert any("streamed_bytes" in f.message
                   for f in report.failing), \
            [f.message for f in report.failing]
        # and the guarded original is clean
        clean = _lint(tmp_path / "c", {"server/endpoint.py": real},
                      ["shared-state-races"])
        assert clean.failing == []

    def test_regression_prepared_cache_miss_guard(self, tmp_path):
        """PR 12 true positive: PreparedCache.misses bumped between the
        two lock blocks.  Reverting the guard (with the real endpoint
        supplying the connection-handler thread roots) re-fires."""
        real_ep = open(os.path.join(
            REPO, "spark_rapids_tpu", "server", "endpoint.py")).read()
        real_pc = open(os.path.join(
            REPO, "spark_rapids_tpu", "server", "prepared.py")).read()
        bad = real_pc.replace(
            "        with self._lock:\n"
            "            self.misses += 1\n",
            "        self.misses += 1\n")
        assert bad != real_pc
        report = _lint(tmp_path, {"server/endpoint.py": real_ep,
                                  "server/prepared.py": bad},
                       ["shared-state-races"])
        assert any("misses" in f.message for f in report.failing), \
            [f.message for f in report.failing]
        clean = _lint(tmp_path / "c", {"server/endpoint.py": real_ep,
                                       "server/prepared.py": real_pc},
                      ["shared-state-races"])
        assert clean.failing == []


class TestTypestate:
    def test_use_after_close_on_spooled_stream(self, tmp_path):
        report = _lint(tmp_path, {"server/bad.py": (
            "def f(mem, d):\n"
            "    s = ResultStream('q', mem, d)\n"
            "    s.put(b'x')\n"
            "    s.close()\n"
            "    s.put(b'y')\n")}, ["typestate"])
        assert len(report.failing) == 1
        assert "use-after-close" in report.failing[0].message
        assert report.failing[0].line == 5

    def test_double_release_on_cached_build_handle(self, tmp_path):
        report = _lint(tmp_path, {"plan/bad.py": (
            "def f(cache, key):\n"
            "    h = cache.lookup_broadcast(key)\n"
            "    h.close()\n"
            "    h.close()\n")}, ["typestate"])
        assert len(report.failing) == 1
        assert "double-release" in report.failing[0].message

    def test_maybe_closed_branch_not_flagged(self, tmp_path):
        """A finding needs the op invalid in EVERY possible state —
        close on one branch only is a maybe, not a definite bug."""
        report = _lint(tmp_path, {"plan/ok.py": (
            "def f(cache, key, flag):\n"
            "    h = cache.lookup_broadcast(key)\n"
            "    if flag:\n"
            "        h.close()\n"
            "        return None\n"
            "    out = h.get()\n"
            "    h.close()\n"
            "    return out\n")}, ["typestate"])
        assert report.failing == []

    def test_finally_close_then_no_touch_clean(self, tmp_path):
        report = _lint(tmp_path, {"memory/ok.py": (
            "def f(catalog, b):\n"
            "    h = catalog.register(b)\n"
            "    try:\n"
            "        return h.get()\n"
            "    finally:\n"
            "        h.close()\n")}, ["typestate"])
        assert report.failing == []

    def test_escape_of_closed_handle_flagged(self, tmp_path):
        report = _lint(tmp_path, {"memory/bad.py": (
            "def f(catalog, b, out):\n"
            "    h = catalog.register(b)\n"
            "    h.close()\n"
            "    out.adopt(h)\n")}, ["typestate"])
        assert len(report.failing) == 1
        assert "escapes" in report.failing[0].message

    def test_use_before_init_two_phase(self, tmp_path):
        report = _lint(tmp_path, {"server/bad.py": (
            "def f(session):\n"
            "    d = SqlFrontDoor(session)\n"
            "    d.begin_drain()\n"
            "def ok(session):\n"
            "    d = SqlFrontDoor(session)\n"
            "    d.start()\n"
            "    d.begin_drain()\n")}, ["typestate"])
        assert [f.line for f in report.failing] == [3]
        assert "use-before-init" in report.failing[0].message

    def test_reasoned_suppression(self, tmp_path):
        report = _lint(tmp_path, {"server/ok.py": (
            "def f(mem, d):\n"
            "    s = ResultStream('q', mem, d)\n"
            "    s.close()\n"
            "    s.put(b'y')  # srtlint: ignore[typestate] (put on a closed stream is the producer's documented stop signal in this probe)\n")},
            ["typestate"])
        assert report.failing == []
        assert len(report.suppressed) == 1


_PROTO_FIXTURE = {
    "server/protocol.py": (
        'REQ_HELLO = b"h"\n'
        'RSP_WELCOME = b"W"\n'
        'RSP_GOAWAY = b"G"\n'     # sent below, never decoded
        'RSP_UNUSED = b"U"\n'     # defined, never sent
        'ERROR_CODES = ("BAD_REQUEST", "DEAD_CODE")\n'
        "class WireError(RuntimeError):\n"
        "    def __init__(self, code, msg):\n"
        "        self.code = code\n"),
    "server/endpoint.py": (
        "from . import protocol as P\n"
        "from .protocol import WireError\n"
        "def serve(conn, bad):\n"
        "    ftype, payload = P.recv_frame(conn, expect=(P.REQ_HELLO,))\n"
        "    P.send_frame(conn, P.RSP_WELCOME)\n"
        "    P.send_frame(conn, P.RSP_GOAWAY)\n"
        "    if bad:\n"
        "        raise WireError('BAD_REQUEST', 'malformed')\n"
        "    raise WireError('NOT_IN_REGISTRY', 'oops')\n"),
    "server/client.py": (
        "from . import protocol as P\n"
        "def hello(sock):\n"
        "    P.send_frame(sock, P.REQ_HELLO)\n"
        "    ftype, payload = P.recv_frame(sock,\n"
        "                                  expect=(P.RSP_WELCOME,))\n"
        "    return ftype\n"
        "def dispatch(e):\n"
        "    return e.code == 'TYPO_CODE'\n"),
}


class TestProtocolConformance:
    def test_wire_drift_classes(self, tmp_path):
        report = _lint(tmp_path, _PROTO_FIXTURE,
                       ["protocol-conformance"])
        msgs = sorted(f.message for f in report.failing)
        # sent but no decoder handles it (the GOAWAY drift class)
        assert any("RSP_GOAWAY is sent here but no decoder" in m
                   for m in msgs)
        # defined but nobody sends it
        assert any("dead frame type: RSP_UNUSED" in m for m in msgs)
        # constructed code missing from the registry
        assert any("'NOT_IN_REGISTRY' is constructed here" in m
                   for m in msgs)
        # registered code nobody constructs
        assert any("dead error code: 'DEAD_CODE'" in m for m in msgs)
        # dispatch comparison against an unregistered code
        assert any("'TYPO_CODE'" in m and "never match" in m
                   for m in msgs)
        assert len(report.failing) == 5

    def test_unhandled_error_code_fixed_by_registration(self, tmp_path):
        files = dict(_PROTO_FIXTURE)
        files["server/protocol.py"] = files["server/protocol.py"] \
            .replace('("BAD_REQUEST", "DEAD_CODE")',
                     '("NOT_IN_REGISTRY", "TYPO_CODE", "BAD_REQUEST")')
        files["server/endpoint.py"] = files["server/endpoint.py"] \
            .replace("    P.send_frame(conn, P.RSP_GOAWAY)\n", "") \
            .replace("raise WireError('NOT_IN_REGISTRY', 'oops')",
                     "raise WireError('BAD_REQUEST', 'oops')")
        files["server/client.py"] = files["server/client.py"] \
            .replace("'TYPO_CODE'", "'BAD_REQUEST'")
        report = _lint(tmp_path, files, ["protocol-conformance"])
        msgs = sorted(f.message for f in report.failing)
        # only the dead vocabulary remains
        assert all("dead" in m for m in msgs), msgs

    def test_dcn_op_vocabulary(self, tmp_path):
        report = _lint(tmp_path, {"parallel/dcn.py": (
            'DCN_OPS = ("fetch", "journal", "ghost")\n'
            "def client(sock):\n"
            "    _send(sock, {'op': 'fetch'})\n"
            "    _send(sock, {'op': 'journal'})\n"
            "    _send(sock, {'op': 'mystery'})\n"
            "def serve(msg):\n"
            "    op = msg.get('op')\n"
            "    if op == 'fetch':\n"
            "        return 1\n"
            "    if op != 'journal':\n"
            "        return 0\n")}, ["protocol-conformance"])
        msgs = sorted(f.message for f in report.failing)
        assert any("'mystery' is sent here but no dispatch" in m
                   for m in msgs)
        assert any("'mystery' is sent here but missing from DCN_OPS"
                   in m for m in msgs)
        assert any("dead DCN op: 'ghost'" in m for m in msgs)

    def test_reasoned_suppression(self, tmp_path):
        files = dict(_PROTO_FIXTURE)
        files["server/endpoint.py"] = files["server/endpoint.py"] \
            .replace(
                "    P.send_frame(conn, P.RSP_GOAWAY)\n",
                "    P.send_frame(conn, P.RSP_GOAWAY)  # srtlint: ignore[protocol-conformance] (decoded by the out-of-tree ops client)\n")
        report = _lint(tmp_path, files, ["protocol-conformance"])
        assert not any("RSP_GOAWAY" in f.message for f in report.failing)
        assert any("RSP_GOAWAY" in f.message for f in report.suppressed)

    def test_real_registries_exist(self):
        """The canonical vocabularies the pass checks against."""
        from spark_rapids_tpu.server import protocol as P
        from spark_rapids_tpu.parallel import dcn
        assert "DRAINING" in P.ERROR_CODES
        assert set(dcn._COORD_OPS) < set(dcn.DCN_OPS)
        assert "fetch" in dcn.DCN_OPS and "journal" in dcn.DCN_OPS


_METRICS_FIXTURE = {
    "utils/telemetry.py": (
        "METRICS = (\n"
        '    ("hits_total", "counter", "", "hits"),\n'
        '    ("dead_gauge", "gauge", "", "nobody emits this"),\n'
        '    ("folded_total", "counter", "", "fold target"),\n'
        ")\n"
        "_QS_FOLD = (\n"
        '    ("hits", "folded_total"),\n'
        ")\n"
        "def count(name, amount=1, **labels):\n"
        "    pass\n"
        "def gauge_set(name, value, **labels):\n"
        "    pass\n"
        "def observe(name, value, **labels):\n"
        "    pass\n"),
    "service/user.py": (
        "from ..utils import telemetry\n"
        "def f(kind):\n"
        "    telemetry.count('hits_total')\n"
        "    telemetry.count('unregistered_total')\n"
        "    telemetry.gauge_set('made_' + kind, 1.0)\n"),
}


class TestMetricsRegistry:
    def test_two_way_vocabulary(self, tmp_path):
        report = _lint(tmp_path, _METRICS_FIXTURE, ["metrics-registry"])
        msgs = sorted(f.message for f in report.failing)
        # unregistered at a call site
        assert any("'unregistered_total' is emitted here but not "
                   "registered" in m for m in msgs)
        # runtime-assembled name
        assert any("assembled at runtime" in m for m in msgs)
        # registered but never emitted (fold targets count as emitted)
        assert any("dead metric vocabulary: 'dead_gauge'" in m
                   for m in msgs)
        assert not any("folded_total" in m for m in msgs)
        assert not any("'hits_total'" in m for m in msgs)
        assert len(report.failing) == 3

    def test_registration_fixes_use_and_emitter_fixes_dead(
            self, tmp_path):
        files = dict(_METRICS_FIXTURE)
        files["utils/telemetry.py"] = files["utils/telemetry.py"] \
            .replace('    ("dead_gauge", "gauge", "", "nobody emits '
                     'this"),\n',
                     '    ("unregistered_total", "counter", "", '
                     '"now registered"),\n')
        files["service/user.py"] = (
            "from ..utils import telemetry\n"
            "def f():\n"
            "    telemetry.count('hits_total')\n"
            "    telemetry.count('unregistered_total')\n")
        report = _lint(tmp_path, files, ["metrics-registry"])
        assert report.failing == [], [f.message for f in report.failing]

    def test_reasoned_suppression(self, tmp_path):
        files = dict(_METRICS_FIXTURE)
        files["service/user.py"] = files["service/user.py"] \
            .replace(
                "    telemetry.count('unregistered_total')\n",
                "    telemetry.count('unregistered_total')  # srtlint: ignore[metrics-registry] (emitted for an out-of-tree dashboard)\n") \
            .replace(
                "    telemetry.gauge_set('made_' + kind, 1.0)\n", "")
        files["utils/telemetry.py"] = files["utils/telemetry.py"] \
            .replace('    ("dead_gauge", "gauge", "", "nobody emits '
                     'this"),\n', "")
        report = _lint(tmp_path, files, ["metrics-registry"])
        assert report.failing == [], [f.message for f in report.failing]
        assert any("unregistered_total" in f.message
                   for f in report.suppressed)

    def test_real_registry_exists(self):
        """The canonical table the pass checks against, and its
        runtime enforcement."""
        from spark_rapids_tpu.utils import telemetry
        names = {m[0] for m in telemetry.METRICS}
        assert "queries_shed_total" in names
        assert "slo_burn_rate" in names
        for _field, metric in telemetry._QS_FOLD:
            assert metric in names, metric
        with pytest.raises(KeyError):
            telemetry.count("never_registered_total")


_MARKS_FIXTURE = {
    "utils/telemetry.py": (
        "METRICS = (\n"
        '    ("hits_total", "counter", "", "hits"),\n'
        ")\n"
        "_QS_FOLD = ()\n"
        "def count(name, amount=1, **labels):\n"
        "    pass\n"),
    "utils/tracing.py": (
        'MARK_PREFIXES = ("perf:", "compile:")\n'
        "MARKS = (\n"
        '    ("perf:anomaly", "root-cause verdict"),\n'
        '    ("compile:storm", "storm detector"),\n'
        '    ("compile:dead", "nobody emits this"),\n'
        ")\n"
        "def mark(op_id, name, cat='mark', **args):\n"
        "    pass\n"
        "def record(op_id, name, cat, t0, dur, **args):\n"
        "    pass\n"),
    "utils/user.py": (
        "from . import telemetry, tracing\n"
        "def f(tr):\n"
        "    telemetry.count('hits_total')\n"
        "    tracing.mark(None, 'perf:anomaly', 'mark')\n"
        "    tr.add_event(None, 'compile:storm', 'compile', 0.0, 0.0)\n"
        "    tr.add_event(None, 'perf:bogus', 'mark', 0.0, 0.0)\n"
        "    tracing.mark(None, 'query:free_form')\n"),
}


class TestMarkVocabulary:
    """The metrics-registry pass's governed trace-mark leg (the
    flight recorder's ``perf:`` / ``compile:`` namespaces)."""

    def test_two_way_mark_vocabulary(self, tmp_path):
        report = _lint(tmp_path, _MARKS_FIXTURE, ["metrics-registry"])
        msgs = sorted(f.message for f in report.failing)
        # a governed-prefix mark minted at an emit site (add_event
        # form) without a MARKS entry
        assert any("'perf:bogus' is emitted here but not registered"
                   in m for m in msgs)
        # a MARKS entry nobody emits
        assert any("dead mark vocabulary: 'compile:dead'" in m
                   for m in msgs)
        # registered marks emitted via tracing.mark AND .add_event
        # both count as used; ungoverned namespaces stay free-form
        assert not any("perf:anomaly" in m for m in msgs)
        assert not any("compile:storm" in m for m in msgs)
        assert not any("query:free_form" in m for m in msgs)
        assert len(report.failing) == 2, msgs

    def test_registration_and_suppression(self, tmp_path):
        files = dict(_MARKS_FIXTURE)
        files["utils/tracing.py"] = files["utils/tracing.py"].replace(
            '    ("compile:dead", "nobody emits this"),\n', "")
        files["utils/user.py"] = files["utils/user.py"].replace(
            "    tr.add_event(None, 'perf:bogus', 'mark', 0.0, 0.0)\n",
            "    tr.add_event(None, 'perf:bogus', 'mark', 0.0, 0.0)"
            "  # srtlint: ignore[metrics-registry] (prototyped mark "
            "for an out-of-tree consumer)\n")
        report = _lint(tmp_path, files, ["metrics-registry"])
        assert report.failing == [], [f.message for f in report.failing]
        assert any("perf:bogus" in f.message
                   for f in report.suppressed)

    def test_fixture_trees_without_tracing_stay_exempt(self, tmp_path):
        """A tree with no utils/tracing.py (older trees, other lint
        fixtures) gets no mark findings at all."""
        files = {k: v for k, v in _MARKS_FIXTURE.items()
                 if k != "utils/tracing.py"}
        report = _lint(tmp_path, files, ["metrics-registry"])
        assert report.failing == [], [f.message for f in report.failing]

    def test_real_mark_vocabulary(self):
        """The canonical MARKS table governs exactly the recorder's
        namespaces, and every entry is under a governed prefix."""
        from spark_rapids_tpu.utils import tracing
        names = {m[0] for m in tracing.MARKS}
        assert "perf:anomaly" in names
        assert "compile:storm" in names
        for name in names:
            assert name.startswith(tracing.MARK_PREFIXES), name


class TestBaselineDrift:
    def test_rewrap_keeps_baseline_entry(self, tmp_path):
        """A pure reformat (re-indent + re-wrap across lines) of a
        baselined statement keeps its entry alive — the key hashes the
        whole statement with whitespace stripped, not the first line."""
        files = {"plan/bad.py": (
            "import jax\n"
            "a = jax.device_get(make_value(1, 2))\n")}
        root = _tree(tmp_path, files)
        bl = str(tmp_path / "baseline.json")
        report = lint_run(root, roots=("spark_rapids_tpu",),
                          rules=["blocking-fetch"], baseline_path=bl)
        engine.write_baseline(report.failing, bl)
        (tmp_path / "spark_rapids_tpu" / "plan" / "bad.py").write_text(
            "import jax\n"
            "a = jax.device_get(\n"
            "        make_value(1,\n"
            "                   2))\n")
        moved = lint_run(root, roots=("spark_rapids_tpu",),
                         rules=["blocking-fetch"], baseline_path=bl)
        assert moved.failing == []
        assert len(moved.baselined) == 1


class TestIncremental:
    def _seed(self, tmp_path):
        files = {
            "plan/a.py": "import jax\ndef f(x):\n    return x\n",
            "plan/b.py": ("from .a import f\n"
                          "def g(x):\n    return f(x)\n"),
            "ops/c.py": ("import numpy as np\n"
                         "def h(col):\n"
                         "    return np.asarray(col.data)  # choke-point-ok (host column; fixture)\n"),
        }
        return _tree(tmp_path, files)

    def test_cold_then_noop_then_edit(self, tmp_path):
        from tools.srtlint.incremental import run_incremental
        root = self._seed(tmp_path)
        cold = run_incremental(root, roots=("spark_rapids_tpu",))
        assert cold.failing == []
        assert len(cold.suppressed) == 1   # the choke-point-ok marker
        assert cold.incremental["cone"] == 3
        # unchanged tree: nothing re-analyzed, cache carries reasons
        noop = run_incremental(root, roots=("spark_rapids_tpu",))
        assert noop.incremental["cone"] == 0
        assert noop.incremental["parsed"] == 0
        assert noop.failing == []
        assert len(noop.suppressed) == 1
        assert noop.suppressed[0].suppress_reason
        # a one-file edit introducing a finding re-verifies without a
        # full re-analysis: only the edited file (plus its reverse-
        # dependency cone) is re-parsed
        (tmp_path / "spark_rapids_tpu" / "plan" / "a.py").write_text(
            "import jax\ndef f(x):\n    return jax.device_get(x)\n")
        edit = run_incremental(root, roots=("spark_rapids_tpu",))
        assert [f.path for f in edit.failing] == ["spark_rapids_tpu/plan/a.py"]
        assert edit.incremental["changed"] == 1
        assert edit.incremental["cone"] == 2      # a.py + dependent b.py
        # c.py is parsed only because the package-scoped global passes
        # re-run; its per-file verdict (the suppression) comes from the
        # cache, not a re-analysis
        assert len(edit.suppressed) == 1
        assert edit.suppressed[0].suppress_reason

    def test_reverse_dependency_cone_gates_global_passes(self, tmp_path):
        from tools.srtlint import incremental as incr
        root = self._seed(tmp_path)
        incr.run_incremental(root, roots=("spark_rapids_tpu",))
        # an edit outside every global scope... plan/ is inside the
        # races scope (whole package), so races re-runs; but protocol
        # and lock-discipline scopes are untouched and stay cached
        (tmp_path / "spark_rapids_tpu" / "plan" / "a.py").write_text(
            "import jax\ndef f(x):\n    return x + 1\n")
        edit = incr.run_incremental(root, roots=("spark_rapids_tpu",))
        rerun = set(edit.incremental["global_rerun"])
        assert "shared-state-races" in rerun
        assert "protocol-conformance" not in rerun
        assert "lock-discipline" not in rerun

    def test_single_file_edit_faster_than_cold(self, monkeypatch):
        """Acceptance: on the REAL tree, a one-file edit re-verifies
        incrementally: the per-file passes run on the edited file alone
        and every other file's verdict is the cached one.  The work is
        compared, not the wall seconds of two scans on a machine that
        other test workers load (the package-wide global passes re-run
        in both, so the two times lie within a third of each other)."""
        import shutil
        import tempfile
        from tools.srtlint.incremental import run_incremental
        analyzed = []  # files each per-file pass was handed, per scan

        def counting(run):
            def wrapped(tree):
                analyzed[-1].update(sf.rel for sf in tree.files)
                return run(tree)
            return wrapped
        for mod in engine._load_passes():
            if getattr(mod, "PER_FILE", False):
                monkeypatch.setattr(mod, "run", counting(mod.run))

        def scan(tmp):
            analyzed.append(set())
            return run_incremental(tmp)

        def verdicts(report, but):
            return sorted((f.path, f.rule, f.line, f.suppress_reason)
                          for f in report.suppressed if f.path != but)
        with tempfile.TemporaryDirectory() as tmp:
            for root in ("spark_rapids_tpu", "tools"):
                shutil.copytree(os.path.join(REPO, root),
                                os.path.join(tmp, root))
            os.makedirs(os.path.join(tmp, "docs"), exist_ok=True)
            shutil.copy(os.path.join(REPO, "docs", "configs.md"),
                        os.path.join(tmp, "docs", "configs.md"))
            cold = scan(tmp)
            assert cold.failing == []
            assert cold.incremental["changed"] == cold.files
            assert len(analyzed[-1]) == cold.files
            edited = "spark_rapids_tpu/ops/cast.py"
            with open(os.path.join(tmp, edited), "a") as f:
                f.write("\n# innocuous trailing comment\n")
            warm = scan(tmp)
            assert warm.failing == []
            assert warm.incremental["changed"] == 1
            assert analyzed[-1] == {edited}
            # the other files' verdicts: not re-analyzed (above), and
            # all there, reasons included
            assert verdicts(cold, edited)
            assert verdicts(warm, edited) == verdicts(cold, edited)


class TestSarifAndChanged:
    def test_sarif_output(self, tmp_path, capsys):
        root = _tree(tmp_path, {"plan/bad.py": (
            "import jax\n"
            "a = jax.device_get(1)\n"
            "b = jax.device_get(2)  # choke-point-ok (fixture seed)\n")})
        out = str(tmp_path / "out.sarif")
        rc = engine.main(["--repo", root, "--full", "--sarif", out])
        capsys.readouterr()
        assert rc == 1
        with open(out) as f:
            sarif = json.load(f)
        assert sarif["version"] == "2.1.0"
        run0 = sarif["runs"][0]
        assert run0["tool"]["driver"]["name"] == "srtlint"
        rules = {r["id"] for r in run0["tool"]["driver"]["rules"]}
        assert "shared-state-races" in rules and "typestate" in rules
        levels = {r["level"] for r in run0["results"]}
        assert levels == {"error", "note"}  # failing + suppressed
        sup = [r for r in run0["results"] if r["level"] == "note"]
        assert sup[0]["suppressions"][0]["justification"]

    def test_changed_scopes_findings(self, tmp_path, capsys):
        import subprocess
        root = _tree(tmp_path, {
            "plan/bad.py": "import jax\na = jax.device_get(1)\n",
            "plan/worse.py": "import jax\nb = jax.device_get(2)\n"})
        subprocess.run(["git", "init", "-q"], cwd=root, check=True)
        subprocess.run(["git", "add", "-A"], cwd=root, check=True)
        subprocess.run(["git", "-c", "user.email=t@t", "-c",
                        "user.name=t", "commit", "-qm", "seed"],
                       cwd=root, check=True)
        # modify ONE of the two offending files
        (tmp_path / "spark_rapids_tpu" / "plan" / "bad.py").write_text(
            "import jax\na = jax.device_get(11)\n")
        rc = engine.main(["--repo", root, "--full", "--changed"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "plan/bad.py" in out
        # the unchanged offender is excluded from the scoped listing
        assert "plan/worse.py" not in out.split("srtlint:")[0]
        assert "1 in changed files" in out


class TestRealTree:
    def test_full_tree_clean_and_within_wall_budget(self):
        """Acceptance: all twelve passes over the real tree, zero
        unsuppressed findings, every suppression reasoned, inside a
        collection-time wall budget."""
        t0 = time.perf_counter()
        report = engine.run(REPO)
        wall = time.perf_counter() - t0
        assert report.failing == [], \
            "\n".join(f"{f.path}:{f.line}: [{f.rule}] {f.message}"
                      for f in report.failing)
        assert report.files > 100
        assert all(f.suppress_reason for f in report.suppressed)
        assert set(report.pass_timings) == set(engine.available_rules())
        assert wall < 30.0, f"full scan took {wall:.1f}s"

    def test_conftest_entry_point_caches(self):
        """The mtime-keyed cache: a second call with an unchanged tree
        must come back from the memo in far under the five regex
        scanners' combined walk time."""
        from tools.srtlint import run_for_pytest
        first = run_for_pytest()
        t0 = time.perf_counter()
        second = run_for_pytest()
        cached_wall = time.perf_counter() - t0
        assert second.failing == first.failing == []
        assert cached_wall < 1.0

    def test_registry_docs_in_sync(self):
        """conf-registry's docs cross-check holds on the real tree —
        docs/configs.md matches TpuConf.help() exactly."""
        from spark_rapids_tpu.config import TpuConf
        with open(os.path.join(REPO, "docs", "configs.md")) as f:
            doc = f.read()
        for line in TpuConf.help().splitlines():
            assert line in doc


class TestShutdownPaths:
    def test_unjoined_attr_thread_detected(self, tmp_path):
        report = _lint(tmp_path, {"service/bad.py": (
            "import threading\n"
            "class S:\n"
            "    def start(self):\n"
            "        self._th = threading.Thread(target=self._loop)\n"
            "        self._th.start()\n"
            "    def close(self):\n"
            "        pass\n")}, ["shutdown-paths"])
        assert [f.line for f in report.failing] == [4]
        assert "never joined" in report.failing[0].message

    def test_join_without_timeout_still_flagged(self, tmp_path):
        """An unbounded join hangs the shutdown a wedged thread was
        supposed to be bounded by."""
        report = _lint(tmp_path, {"server/bad.py": (
            "import threading\n"
            "class S:\n"
            "    def start(self):\n"
            "        self._th = threading.Thread(target=self._loop)\n"
            "        self._th.start()\n"
            "    def close(self):\n"
            "        self._th.join()\n")}, ["shutdown-paths"])
        assert [f.line for f in report.failing] == [4]

    def test_no_handle_escape_detected_and_suppressed(self, tmp_path):
        report = _lint(tmp_path, {"parallel/bad.py": (
            "import threading\n"
            "def fire(fn):\n"
            "    threading.Thread(target=fn).start()\n"
            "def ok(fn):\n"
            "    threading.Thread(target=fn).start()  # srtlint: ignore[shutdown-paths] (hedge loser; socket timeout bounds it)\n")},
            ["shutdown-paths"])
        assert [f.line for f in report.failing] == [3]
        assert "no handle escapes" in report.failing[0].message
        assert len(report.suppressed) == 1

    def test_container_append_joined_in_close_clean(self, tmp_path):
        report = _lint(tmp_path, {"parallel/ok.py": (
            "import threading\n"
            "class S:\n"
            "    def start(self):\n"
            "        t = threading.Thread(target=self._loop)\n"
            "        t.start()\n"
            "        self._threads.append(t)\n"
            "    def close(self):\n"
            "        for t in self._threads:\n"
            "            t.join(timeout=2.0)\n")}, ["shutdown-paths"])
        assert report.failing == []

    def test_dict_store_and_aliased_values_loop_clean(self, tmp_path):
        """The endpoint idiom: store into a dict, join through
        ``list(self._conn_threads.values())`` — two levels of local
        aliasing between the container and the join."""
        report = _lint(tmp_path, {"server/ok.py": (
            "import threading\n"
            "class S:\n"
            "    def accept(self, cid):\n"
            "        th = threading.Thread(target=self._conn)\n"
            "        self._conn_threads[cid] = th\n"
            "        th.start()\n"
            "    def close(self):\n"
            "        threads = list(self._conn_threads.values())\n"
            "        for th in threads:\n"
            "            th.join(timeout=2.0)\n")}, ["shutdown-paths"])
        assert report.failing == []

    def test_same_function_join_clean(self, tmp_path):
        report = _lint(tmp_path, {"parallel/scatter.py": (
            "import threading\n"
            "def fan_out(fns):\n"
            "    ts = []\n"
            "    for fn in fns:\n"
            "        t = threading.Thread(target=fn)\n"
            "        ts.append(t)\n"
            "        t.start()\n"
            "    for t in ts:\n"
            "        t.join(timeout=30)\n")}, ["shutdown-paths"])
        assert report.failing == []

    def test_outside_serving_layers_ignored(self, tmp_path):
        report = _lint(tmp_path, {"runtime/bg.py": (
            "import threading\n"
            "def fire(fn):\n"
            "    threading.Thread(target=fn).start()\n")},
            ["shutdown-paths"])
        assert report.failing == []

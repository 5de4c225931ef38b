"""Warm-start subsystem: a persistent, shippable compile store with
fingerprint-prioritized prewarm.

The bench snapshots show the engine winning warm while cold paths pay
tens of seconds of XLA compilation per query, and the compile ledger
proves every rolling restart is a recompile storm.  This module closes
the loop with three cooperating parts (docs/warmstart.md):

  * **index** — a content-addressed store of *what this door has
    compiled*: one entry per (statement fingerprint x bucket-ladder
    signature x device topology), recording the statement spec and the
    exact runtime pytree signature of every stage program the
    statement ran (shapes, dtypes, validity-mask presence per column).
    The index layers OVER JAX's persistent compilation cache
    (:func:`setup_jax_cache`): JAX caches the executables by HLO; the
    index remembers which programs a statement NEEDS and what their
    input avals were — the recipe for compiling them again without
    traffic;
  * **persistence + shipping** — the index is an atomic JSON manifest
    (``warmstore.dir``), LRU-bounded (``maxEntries``/``maxBytes``),
    corruption-tolerant on load (a bad manifest counts
    ``warmstore_corrupt_total`` and degrades to empty — the store must
    never fail a door).  A draining door additionally ships its
    hottest entries to its GOAWAY siblings over the wire (REQ_WARM),
    so a failover target warms up before the parked clients arrive;
  * **prewarm** — :func:`prewarm` re-plans each hot entry's spec
    through the prepared cache, walks the physical tree for its stage
    programs, and AOT-compiles each recorded signature
    (``jit.lower(avals).compile()``) into the process program cache
    (:func:`..plan.physical.install_program`).  Priority comes from
    the admission cost model's per-fingerprint traffic profiles,
    falling back to store hit counts; the pass is budgeted
    (``prewarm.budgetS`` / ``prewarm.maxStatements``) and yields to
    live queries between entries (``QueryScheduler.await_idle``), so
    prewarm never starves the device semaphore.  Compiles inside the
    pass run under :func:`..utils.recorder.compile_prewarm_scope`, so
    the ledger classifies them ``prewarm`` and the storm detector
    ignores the burst.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional

log = logging.getLogger("spark_rapids_tpu")

__all__ = ["WarmStore", "setup_jax_cache", "xla_cache_dir", "store_dir",
           "topology_key", "initialize",
           "store", "is_active", "note_statement", "note_program",
           "prewarm", "snapshot", "reset_for_tests"]

_pc = time.perf_counter

_MANIFEST = "manifest.json"
_SAVE_INTERVAL_S = 1.0  # throttle: at most one manifest write per second


# ---------------------------------------------------------------------------------
# Satellite: the XLA persistent-cache hookup (routed here from
# runtime/device.py so one module owns the warm-start disk story).
# ---------------------------------------------------------------------------------

_CACHE_DIR_KEY = "spark.rapids.tpu.xla.cacheDir"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _conf_cache_dir(conf) -> Optional[str]:
    d = conf[_CACHE_DIR_KEY]
    return os.path.join(_CHECKOUT, os.path.expanduser(d)) if d else None


def xla_cache_dir(conf) -> Optional[str]:
    """The persistent XLA cache directory in effect:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else
    ``xla.cacheDir`` with a relative path resolved against the checkout
    (never the working directory, a pid or a time: the cache must be at
    the same place for every process of one checkout).  None = disabled."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or _conf_cache_dir(conf))


def store_dir(conf) -> Optional[str]:
    """Where the warm-start index lives.  The index describes the
    executables in the XLA cache, so a relative ``warmstore.dir`` follows
    that cache wherever :func:`xla_cache_dir` put it.  None = in-memory."""
    d = os.path.expanduser(conf["spark.rapids.tpu.warmstore.dir"])
    if not d or os.path.isabs(d):
        return d or None
    base = xla_cache_dir(conf)
    return os.path.join(base, d) if base else None


def _probe_writable(path: str) -> None:
    """Create ``path`` and prove a file can be written there (raises)."""
    os.makedirs(path, exist_ok=True)
    probe = os.path.join(path, ".srt_write_probe")
    with open(probe, "w") as f:
        f.write("ok")
    os.remove(probe)


def setup_jax_cache(conf) -> bool:
    """Turn on JAX's persistent compilation cache at :func:`xla_cache_dir`.

    With ``JAX_COMPILATION_CACHE_DIR`` set JAX reads the variable itself
    and no directory is set in code.  Otherwise the dir is PROBED for
    writability first; an unwritable path logs, counts
    ``warmstore_errors_total{kind=cache_dir}`` (so a fleet silently
    proceeding cold is visible on /metrics), and returns False — device
    init never fails over a cache."""
    path = xla_cache_dir(conf)
    if not path:
        return False
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if conf.is_set(_CACHE_DIR_KEY) and _conf_cache_dir(conf) != path:
            log.warning("%s=%s ignored: JAX_COMPILATION_CACHE_DIR=%s "
                        "places the cache", _CACHE_DIR_KEY,
                        conf[_CACHE_DIR_KEY], path)
        return True
    from ..utils import telemetry
    try:
        _probe_writable(path)
        jax.config.update("jax_compilation_cache_dir", path)
        return True
    except Exception as e:  # fault-ok (an unwritable cache dir degrades to cold compiles, never fails init)
        log.warning("xla compilation cache unavailable at %s (%s): "
                    "proceeding cold", path, e)
        telemetry.count("warmstore_errors_total", kind="cache_dir")
        return False


def topology_key() -> str:
    """Mesh/topology identity for the content address: programs
    compiled for one device layout never warm-start another."""
    try:
        import jax
        devs = jax.devices()
        kind = str(getattr(devs[0], "device_kind", devs[0].platform))
        return f"{devs[0].platform}:{kind}:{len(devs)}".replace(" ", "_")
    except Exception:  # fault-ok (identity degrades; entries just never match)
        return "unknown"


def _entry_key(fp: str, ladder_sig: str, topo: str) -> str:
    h = hashlib.sha256(f"{fp}|{ladder_sig}|{topo}".encode())
    return h.hexdigest()[:24]


# ---------------------------------------------------------------------------------
# The store.
# ---------------------------------------------------------------------------------

class WarmStore:
    """Content-addressed warm-start index with LRU bounds, atomic
    persistence, and ship/import."""

    def __init__(self, conf):
        self.enabled = bool(conf["spark.rapids.tpu.warmstore.enabled"])
        self.max_entries = conf["spark.rapids.tpu.warmstore.maxEntries"]
        self.max_bytes = conf["spark.rapids.tpu.warmstore.maxBytes"]
        # identity for initialize()'s reuse check: a second door in the
        # same process (the two-door drain/ship shape) must SHARE the
        # live index, not replace it with a stale disk load
        self.conf_key = (self.enabled, self.max_entries, self.max_bytes,
                         store_dir(conf))
        self._lock = threading.RLock()
        self._entries: "OrderedDict[str, dict]" = OrderedDict()
        self._touched: set = set()        # entry keys noted this process
        self._noted_programs: set = set()  # (key, program_key) dedupe
        self._dirty = False
        self._last_save = 0.0
        self._save_failed = False
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.shipped_out = 0
        self.shipped_in = 0
        self.corrupt = 0
        self.prewarmed = 0
        self._topo: Optional[str] = None  # resolved lazily (jax init)
        self._dir: Optional[str] = None
        d = store_dir(conf)
        if self.enabled and d:
            self._dir = self._probe_dir(d)
        if self._dir:
            self._load()

    # -- directory / persistence --------------------------------------------------
    def _probe_dir(self, path: str) -> Optional[str]:
        from ..utils import telemetry
        try:
            _probe_writable(path)
            return path
        except Exception as e:  # fault-ok (unwritable store dir degrades to in-memory)
            log.warning("warmstore dir unusable at %s (%s): "
                        "in-memory only", path, e)
            telemetry.count("warmstore_errors_total", kind="store_dir")
            return None

    def _load(self) -> None:
        """Corruption-tolerant manifest load: a bad file (or bad
        entries inside one) counts and drops — never raises."""
        from ..utils import recorder, telemetry
        path = os.path.join(self._dir, _MANIFEST)
        if not os.path.exists(path):
            return
        try:
            with open(path) as f:
                raw = json.load(f)
            entries = raw["entries"]
            assert isinstance(entries, list)
        except Exception as e:  # fault-ok (a corrupt manifest degrades to an empty store)
            log.warning("warmstore manifest corrupt at %s (%s): "
                        "starting empty", path, e)
            with self._lock:
                self.corrupt += 1
            telemetry.count("warmstore_corrupt_total")
            return
        fps = []
        with self._lock:
            for ent in entries:
                try:
                    key = str(ent["key"])
                    fp = str(ent["fp"])
                    ent["warm"] = True  # a prior life compiled this
                    self._entries[key] = ent
                    fps.append(fp)
                except Exception:  # fault-ok (one bad entry drops; the rest load)
                    self.corrupt += 1
                    telemetry.count("warmstore_corrupt_total")
        # the ledger attributes these fingerprints' next compiles to
        # the store (trigger=store_hit — a disk deserialization via the
        # XLA cache, not a post-restart storm)
        recorder.compile_store_known(fps)

    def _serialize(self) -> str:
        with self._lock:
            return json.dumps(
                {"version": 1, "topo": self._topo,
                 "entries": list(self._entries.values())})

    def approx_bytes(self) -> int:
        return len(self._serialize())

    def _maybe_save(self, force: bool = False) -> None:
        if not self._dir:
            return
        with self._lock:
            if not self._dirty:
                return
            now = _pc()
            if not force and now - self._last_save < _SAVE_INTERVAL_S:
                return
            self._dirty = False
            self._last_save = now
            blob = self._serialize()
        path = os.path.join(self._dir, _MANIFEST)
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                f.write(blob)
            os.replace(tmp, path)  # atomic: readers see old or new
            self._save_failed = False
        except Exception as e:  # fault-ok (persistence is best-effort; the in-memory index keeps serving)
            from ..utils import telemetry
            if not self._save_failed:  # log once per failure streak
                log.warning("warmstore save failed at %s: %s", path, e)
            self._save_failed = True
            telemetry.count("warmstore_errors_total", kind="store_dir")

    def flush(self) -> None:
        self._maybe_save(force=True)

    # -- identity -----------------------------------------------------------------
    def _topology(self) -> str:
        if self._topo is None:
            self._topo = topology_key()
        return self._topo

    def _key_for(self, fp: str) -> str:
        from ..plan import bucketing
        return _entry_key(fp, bucketing.ladder_signature(),
                          self._topology())

    # -- notes from the serving path ----------------------------------------------
    def note_statement(self, fp: Optional[str],
                       spec: Optional[dict] = None) -> None:
        """One statement arrived (prepare or query): find-or-create its
        entry.  First touch of an entry a PRIOR life persisted (or a
        sibling shipped) is a warm hit; a statement with no entry is a
        miss and seeds one."""
        if not self.enabled or not fp:
            return
        from ..plan import bucketing
        from ..utils import telemetry
        key = self._key_for(fp)
        with self._lock:
            ent = self._entries.get(key)
            first_touch = key not in self._touched
            self._touched.add(key)
            if ent is None:
                self.misses += 1
                ent = self._entries[key] = {
                    "key": key, "fp": fp,
                    "ladder": bucketing.ladder_signature(),
                    "topo": self._topology(),
                    "hits": 0, "programs": {},
                    "created": time.time(), "warm": False}
                hit = False
            else:
                hit = first_touch and bool(ent.get("warm"))
                if hit:
                    self.hits += 1
            ent["hits"] = int(ent.get("hits", 0)) + 1
            ent["last"] = time.time()
            if spec is not None and ent.get("spec") is None:
                ent["spec"] = spec
            self._entries.move_to_end(key)
            self._dirty = True
            self._evict_locked()
        if first_touch:
            telemetry.count("warmstore_hits_total" if hit
                            else "warmstore_misses_total")
        self._maybe_save()

    def note_program(self, program_key: str, fp: str, sig: dict,
                     capacity: int) -> None:
        """Record one stage program's runtime pytree signature under
        the current statement's entry — the aval recipe prewarm
        replays.  Deduped per (entry, program) so the per-batch hot
        path pays one set lookup after the first."""
        if not self.enabled or not fp:
            return
        from ..plan import bucketing
        key = self._key_for(fp)
        dedupe = (key, program_key)
        with self._lock:
            if dedupe in self._noted_programs:
                return
            self._noted_programs.add(dedupe)
            ent = self._entries.get(key)
            if ent is None:
                return  # statement never noted (disabled mid-flight)
            ent.setdefault("programs", {})[program_key] = {
                "sig": sig,
                "bucket": bucketing.bucket_signature(capacity)}
            self._dirty = True
        self._maybe_save()

    def seen_program(self, program_key: str, fp: str) -> bool:
        """Cheap hot-path guard: True once (entry, program) is noted."""
        with self._lock:
            return (self._key_for(fp), program_key) \
                in self._noted_programs

    # -- LRU ----------------------------------------------------------------------
    def _evict_locked(self) -> None:
        from ..utils import telemetry
        evicted = 0
        while len(self._entries) > max(1, self.max_entries):
            self._entries.popitem(last=False)
            evicted += 1
        if self.max_bytes and len(self._entries) > 1:
            while len(self._entries) > 1 and \
                    len(self._serialize()) > self.max_bytes:
                self._entries.popitem(last=False)
                evicted += 1
        if evicted:
            self.evictions += evicted
            self._dirty = True
            for _ in range(evicted):
                telemetry.count("warmstore_evictions_total")

    # -- shipping -----------------------------------------------------------------
    def export_hot(self, n: int) -> List[dict]:
        """The ship payload: the n hottest entries (by hit count) that
        carry a replayable spec."""
        with self._lock:
            cands = [e for e in self._entries.values() if e.get("spec")]
            cands.sort(key=lambda e: int(e.get("hits", 0)), reverse=True)
            return [dict(e) for e in cands[:max(0, n)]]

    def import_shipped(self, entries: List[dict]) -> int:
        """Merge a sibling's shipped entries.  Entries re-key to the
        LOCAL topology (the sibling's executables don't transfer — its
        *recipes* do; prewarm recompiles them here), keep the max hit
        count on collision, and prime the ledger: these fingerprints'
        next compiles are the warm path working, not a storm."""
        from ..utils import recorder, telemetry
        imported = 0
        fps = []
        with self._lock:
            for ent in entries:
                try:
                    fp = str(ent["fp"])
                    ladder = str(ent.get("ladder", ""))
                    key = _entry_key(fp, ladder, self._topology())
                    old = self._entries.get(key)
                    new = dict(ent)
                    new["key"] = key
                    new["topo"] = self._topology()
                    new["warm"] = True
                    if old is not None:
                        new["hits"] = max(int(old.get("hits", 0)),
                                          int(new.get("hits", 0)))
                        progs = dict(old.get("programs") or {})
                        progs.update(new.get("programs") or {})
                        new["programs"] = progs
                    self._entries[key] = new
                    self._entries.move_to_end(key)
                    imported += 1
                    fps.append(fp)
                except Exception:  # fault-ok (one bad shipped entry drops; the rest import)
                    self.corrupt += 1
                    telemetry.count("warmstore_corrupt_total")
            self.shipped_in += imported
            self._dirty = imported > 0
            self._evict_locked()
        for _ in range(imported):
            telemetry.count("warmstore_shipped_total",
                            direction="received")
        recorder.compile_store_known(fps)
        self._maybe_save(force=True)
        return imported

    # -- prewarm candidates -------------------------------------------------------
    def prewarm_candidates(self, cost_model=None) -> List[dict]:
        """Entries worth prewarming (spec + recorded programs, not yet
        touched live this process), hottest first.  Priority: the
        admission cost model's traffic profile (arrivals x expected
        runtime) when it knows the fingerprint, else store hits."""
        with self._lock:
            cands = [dict(e) for e in self._entries.values()
                     if e.get("spec") and e.get("programs")
                     and e["key"] not in self._touched]

        def score(e):
            if cost_model is not None:
                prof = cost_model.predict(e["fp"])
                if prof is not None and prof.samples:
                    return prof.samples * max(prof.runtime_s, 1e-3)
            return float(e.get("hits", 0))

        cands.sort(key=score, reverse=True)
        return cands

    def fingerprints(self) -> List[str]:
        """Every statement fingerprint the index knows (full strings —
        the snapshot truncates for display)."""
        with self._lock:
            return [str(e.get("fp", "")) for e in self._entries.values()]

    def note_prewarmed(self, key: str) -> None:
        with self._lock:
            self.prewarmed += 1
            ent = self._entries.get(key)
            if ent is not None:
                ent["warm"] = True

    # -- observability ------------------------------------------------------------
    def export_gauges(self) -> None:
        from ..utils import telemetry
        with self._lock:
            n = len(self._entries)
        telemetry.gauge_set("warmstore_entries", float(n))
        telemetry.gauge_set("warmstore_bytes", float(self.approx_bytes()))

    def snapshot(self, top: int = 20) -> Dict[str, Any]:
        with self._lock:
            entries = sorted(self._entries.values(),
                             key=lambda e: int(e.get("hits", 0)),
                             reverse=True)
            return {
                "enabled": self.enabled,
                "dir": self._dir or "",
                "persistent": bool(self._dir),
                "topology": self._topology(),
                "entries": len(self._entries),
                "bytes": self.approx_bytes(),
                "max_entries": self.max_entries,
                "max_bytes": self.max_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "shipped_in": self.shipped_in,
                "shipped_out": self.shipped_out,
                "prewarmed": self.prewarmed,
                "corrupt": self.corrupt,
                "top": [{
                    "key": e["key"],
                    "fingerprint": str(e.get("fp", ""))[:16],
                    "hits": int(e.get("hits", 0)),
                    "programs": len(e.get("programs") or {}),
                    "warm": bool(e.get("warm")),
                    "has_spec": e.get("spec") is not None,
                } for e in entries[:top]],
            }


# ---------------------------------------------------------------------------------
# Module singleton (armed by the front door / tests; session-direct use
# stays disarmed and every hook no-ops).
# ---------------------------------------------------------------------------------

_STORE: Optional[WarmStore] = None
_STORE_LOCK = threading.Lock()


def initialize(conf) -> Optional[WarmStore]:
    """Create (or adopt) the process store from a conf.  A second door
    in the same process with the SAME warmstore conf shares the live
    index (replacing it would discard unsaved programs and double-count
    warm hits); a different conf flushes the old store and swaps.
    Returns the active store, or None when ``warmstore.enabled`` is
    off."""
    global _STORE
    with _STORE_LOCK:
        if not conf["spark.rapids.tpu.warmstore.enabled"]:
            old, _STORE = _STORE, None
        else:
            conf_key = (True,
                        conf["spark.rapids.tpu.warmstore.maxEntries"],
                        conf["spark.rapids.tpu.warmstore.maxBytes"],
                        store_dir(conf))
            if _STORE is not None and _STORE.conf_key == conf_key:
                return _STORE
            old, _STORE = _STORE, WarmStore(conf)
    if old is not None:
        old.flush()
    return _STORE


def store() -> Optional[WarmStore]:
    return _STORE


def is_active() -> bool:
    st = _STORE
    return st is not None and st.enabled


def note_statement(fp: Optional[str], spec: Optional[dict] = None) -> None:
    st = _STORE
    if st is not None:
        st.note_statement(fp, spec)


def note_program(program_key: str, arrays, extras, sel,
                 ansi: bool) -> None:
    """Hot-path hook (plan/physical.StageExec.run_one): record the
    pytree signature of one stage program call under the current
    statement.  One set lookup per batch after the first."""
    st = _STORE
    if st is None:
        return
    from ..service import cancel
    ctl = cancel.current()
    fp = getattr(ctl, "fingerprint", None) if ctl is not None else None
    if not fp:
        return
    if st.seen_program(program_key, fp):
        return
    capacity = 0

    def aval(x):
        return {"shape": list(x.shape), "dtype": str(x.dtype)}

    def pair(p):
        nonlocal capacity
        if p is None:
            return None
        data, valid = p
        capacity = capacity or int(data.shape[0])
        return {"data": aval(data),
                "valid": aval(valid) if valid is not None else None}

    sig = {"arrays": [pair(a) for a in arrays],
           "extras": [pair(e) for e in extras],
           "sel": aval(sel) if sel is not None else None,
           "ansi": bool(ansi)}
    st.note_program(program_key, fp, sig, capacity)


def snapshot() -> Optional[Dict[str, Any]]:
    st = _STORE
    return st.snapshot() if st is not None else None


def _export_gauges() -> None:
    st = _STORE
    if st is not None:
        st.export_gauges()


from ..utils import telemetry as _telemetry  # noqa: E402 (after the state it exports)

_telemetry.register_provider(_export_gauges)


def reset_for_tests() -> None:
    global _STORE
    with _STORE_LOCK:
        _STORE = None


def simulate_restart(conf) -> Optional[WarmStore]:
    """The in-process door-restart simulation (loadgen --restart-probe
    and the restart-differential tests): flush and DROP the live store,
    then re-initialize from disk exactly as a fresh process would —
    entries come back ``warm``, the compile ledger learns the
    store-known fingerprints, and the prewarm lane sees them untouched.
    Callers pair this with ``plan.physical.clear_program_cache()`` to
    lose the compiled programs a real restart loses."""
    global _STORE
    with _STORE_LOCK:
        old, _STORE = _STORE, None
    if old is not None:
        old.flush()
    return initialize(conf)


# ---------------------------------------------------------------------------------
# Prewarm.
# ---------------------------------------------------------------------------------

class _AotProgram:
    """An ahead-of-time compiled stage program installed into the
    process program cache.  Calls with the recorded avals hit the AOT
    executable; anything else falls back to a fresh jit of the same
    build (which traces/compiles for the new shapes exactly as the
    cold path would — correctness never depends on the AOT hit)."""

    def __init__(self, compiled, fallback):
        self._compiled = compiled
        self._fallback = fallback

    def __call__(self, *args):
        try:
            return self._compiled(*args)
        except (TypeError, ValueError):  # aval mismatch → live path
            return self._fallback(*args)


def _aot_compile(stage, in_schema, sig: dict):
    """jit.lower(avals).compile() one recorded stage-program signature;
    returns an installable callable."""
    import jax
    import numpy as np

    def sds(d):
        return jax.ShapeDtypeStruct(tuple(d["shape"]),
                                    np.dtype(d["dtype"]))

    def pair(p):
        if p is None:
            return None
        return (sds(p["data"]),
                sds(p["valid"]) if p.get("valid") else None)

    arrays = tuple(pair(a) for a in sig["arrays"])
    extras = tuple(pair(e) for e in sig["extras"])
    sel = sds(sig["sel"]) if sig.get("sel") else None
    nr = jax.ShapeDtypeStruct((), np.dtype("int32"))
    build = stage._build_fn(in_schema, ansi=bool(sig.get("ansi")))
    from ..plan.physical import program
    jitted = program("stage", build)
    compiled = jitted.lower(arrays, extras, sel, nr).compile()
    # install_program wraps the pair in the program's span: the
    # fallback is the bare jit, not to span it twice
    return _AotProgram(compiled, jitted.call)


def _walk_stages(node):
    from ..plan.physical import StageExec
    if isinstance(node, StageExec):
        yield node
    for c in getattr(node, "children", ()):
        yield from _walk_stages(c)


def _prewarm_entry(session, prepared, tables, conf, ent: dict) -> int:
    """Re-plan one entry's spec and AOT-compile its recorded stage
    programs into the process cache.  Returns programs compiled."""
    from ..plan import physical
    stmt, _ = prepared.prepare(session, ent["spec"], tables, conf)
    ansi = conf["spark.rapids.tpu.sql.ansi.enabled"]
    programs = ent.get("programs") or {}
    compiled = 0
    for stage in _walk_stages(stmt.phys):
        fp = stage.fingerprint() + ("|ansi" if ansi else "")
        key = "stage|" + fp
        rec = programs.get(key)
        # an older manifest may hold the signature of a stage program
        # that donated its inputs: no such program exists, the entry is
        # skipped
        if rec is None or rec["sig"].get("donate") \
                or physical.has_program(key):
            continue
        fn = _aot_compile(stage, stage.children[0].output_schema,
                          rec["sig"])
        physical.install_program(key, "stage", fn)
        compiled += 1
    return compiled


def prewarm(session, prepared, tables, conf, scheduler=None,
            stop: Optional[threading.Event] = None) -> Dict[str, Any]:
    """One budgeted prewarm pass over the store's hot head.

    Runs on a background thread at door startup and after a shipped
    import.  Between entries the pass yields to live traffic
    (``scheduler.await_idle``) and re-checks the wall budget, so a
    burst of queued queries always wins the device semaphore."""
    from ..utils import recorder, telemetry
    st = _STORE
    out = {"prewarmed": 0, "programs": 0, "errors": 0, "skipped": 0,
           "elapsed_s": 0.0}
    if st is None or not st.enabled \
            or not conf["spark.rapids.tpu.warmstore.prewarm.enabled"]:
        return out
    budget_s = conf["spark.rapids.tpu.warmstore.prewarm.budgetS"]
    max_n = conf["spark.rapids.tpu.warmstore.prewarm.maxStatements"]
    cost_model = None
    if scheduler is not None:
        cost_model = getattr(getattr(scheduler, "admission", None),
                             "cost_model", None)
    cands = st.prewarm_candidates(cost_model)
    t0 = _pc()
    for ent in cands:
        if out["prewarmed"] >= max_n or _pc() - t0 > budget_s \
                or (stop is not None and stop.is_set()):
            out["skipped"] = len(cands) - out["prewarmed"] \
                - out["errors"]
            break
        if scheduler is not None:
            # the live lane owns the device: wait for an idle window
            # (bounded — a saturated door still prewarms, just slowly)
            scheduler.await_idle(timeout=max(
                0.0, min(5.0, budget_s - (_pc() - t0))))
        try:
            with recorder.compile_prewarm_scope(ent["fp"]):
                n = _prewarm_entry(session, prepared, tables, conf, ent)
            out["programs"] += n
            out["prewarmed"] += 1
            st.note_prewarmed(ent["key"])
            telemetry.count("warmstore_prewarmed_total")
        except Exception as e:  # fault-ok (one entry failing to prewarm must not stop the pass or the door)
            from ..server.spec import BadSpec
            if isinstance(e, BadSpec):
                # a spec this door can't replay (table not registered
                # here — normal in a heterogeneous fleet, or shipped
                # ahead of registration; register_table re-kicks)
                out["skipped"] += 1
                continue
            out["errors"] += 1
            telemetry.count("warmstore_errors_total", kind="prewarm")
            log.warning("warmstore prewarm failed for %s: %s",
                        str(ent.get("fp", ""))[:16], e)
    out["elapsed_s"] = round(_pc() - t0, 4)
    if out["prewarmed"] or out["errors"]:
        log.info("warmstore prewarm: %(prewarmed)d statements, "
                 "%(programs)d programs, %(errors)d errors in "
                 "%(elapsed_s).2fs", out)
    st.flush()
    return out

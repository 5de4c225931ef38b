"""Typed, self-documenting configuration registry.

TPU-native analog of the reference's ``RapidsConf`` (RapidsConf.scala:120-259
``ConfEntry``/``TypedConfBuilder``; 192 ``spark.rapids.*`` keys): every knob is
registered once with a type, default, and doc string; ``TpuConf.help()``
generates the user documentation from the registry
(RapidsConf.scala:2019-2075).  Keys use the ``spark.rapids.tpu.*`` namespace.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

__all__ = ["ConfEntry", "TpuConf", "register", "ALL_ENTRIES"]


@dataclass(frozen=True)
class ConfEntry:
    key: str
    default: Any
    doc: str
    conv: Callable[[str], Any]
    startup_only: bool = False
    internal: bool = False
    check: Optional[Callable[[Any], Optional[str]]] = None

    def convert(self, raw: Any) -> Any:
        if isinstance(raw, str):
            value = self.conv(raw)
        else:
            value = raw
        if self.check is not None:
            err = self.check(value)
            if err:
                raise ValueError(f"invalid value {value!r} for {self.key}: {err}")
        return value


ALL_ENTRIES: Dict[str, ConfEntry] = {}


def _to_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


def register(key: str, default: Any, doc: str, *, conv: Callable = None,
             startup_only: bool = False, internal: bool = False,
             check: Callable = None) -> ConfEntry:
    if conv is None:
        if isinstance(default, bool):
            conv = _to_bool
        elif isinstance(default, int):
            conv = int
        elif isinstance(default, float):
            conv = float
        else:
            conv = str
    entry = ConfEntry(key, default, doc, conv, startup_only, internal, check)
    assert key not in ALL_ENTRIES, f"duplicate conf key {key}"
    ALL_ENTRIES[key] = entry
    return entry


def _one_of(*allowed: str):
    def _check(v):
        if v not in allowed:
            return f"must be one of {allowed}"
        return None
    return _check


# ---------------------------------------------------------------------------------
# Registry.  Grouped to mirror the reference's config surface (docs/configs.md).
# ---------------------------------------------------------------------------------

SQL_ENABLED = register(
    "spark.rapids.tpu.sql.enabled", True,
    "Enable TPU acceleration of SQL/DataFrame execution. When false every "
    "operator runs on the CPU fallback path.")

SQL_MODE = register(
    "spark.rapids.tpu.sql.mode", "executeontpu",
    "Plugin mode: 'executeontpu' runs supported operators on the TPU; "
    "'explainonly' plans as if a TPU were present and reports which operators "
    "would or would not be accelerated, but executes everything on CPU.",
    check=_one_of("executeontpu", "explainonly"))

EXPLAIN = register(
    "spark.rapids.tpu.sql.explain", "NOT_ON_TPU",
    "Explain verbosity for plan conversion: NONE, NOT_ON_TPU (reasons for "
    "fallbacks only), or ALL.",
    check=_one_of("NONE", "NOT_ON_TPU", "ALL"))

BATCH_SIZE_ROWS = register(
    "spark.rapids.tpu.sql.batchSizeRows", 4 << 20,
    "Target number of rows per columnar batch on device. Batches are padded "
    "to the next capacity bucket so XLA executables are reused across "
    "batches. Large batches amortize per-dispatch host↔device round trips "
    "(the analog of the reference's ~1GiB batchSizeBytes target); measured "
    "on TPC-H Q6 @ SF1: 4M rows/batch is ~30% faster than 1M.")

BATCH_SIZE_BYTES = register(
    "spark.rapids.tpu.sql.batchSizeBytes", 1 << 30,
    "Soft target for the in-memory size of a device batch, pre-padding.")

COALESCE_ENABLED = register(
    "spark.rapids.tpu.sql.coalesce.enabled", True,
    "Insert CoalesceBatches operators (GpuCoalesceBatches analog) that "
    "merge small batches to each consumer's declared goal — TargetSize "
    "(batchSizeRows) before aggregates/sorts, RequireSingleBatch before "
    "windows. Amortizes per-batch dispatch and XLA program reuse.")

MIN_CAPACITY = register(
    "spark.rapids.tpu.sql.minBatchCapacity", 1024,
    "Smallest capacity bucket. Device arrays are padded to "
    "power-of-two buckets no smaller than this, bounding executable-cache "
    "cardinality (one compile per op-shape bucket).")

DEVICE_PLATFORM = register(
    "spark.rapids.tpu.device.platform", "",
    "Force a jax platform for device selection (e.g. 'tpu', 'cpu'). "
    "Empty = prefer tpu, else the default backend "
    "(GpuDeviceManager.scala:150 device-acquisition analog).",
    startup_only=True)

CONCURRENT_TASKS = register(
    "spark.rapids.tpu.sql.concurrentTpuTasks", 2,
    "Number of tasks that may hold the TPU semaphore concurrently. The TPU "
    "has no CUDA-stream analog, so this primarily overlaps host I/O of one "
    "task with device compute of another. Reconfigurable at runtime: the "
    "process semaphore resizes in place, so in-flight holders and blocked "
    "waiters survive the change.")

SCHED_MAX_CONCURRENT = register(
    "spark.rapids.tpu.sql.scheduler.maxConcurrent", 2,
    "Queries the service scheduler (service/scheduler.py) runs "
    "concurrently. Each admitted query still takes a concurrentTpuTasks "
    "semaphore permit, so the effective device concurrency is "
    "min(maxConcurrent, concurrentTpuTasks); raising only this knob "
    "queues the excess at the semaphore (cancellable, wait traced).",
    check=lambda v: None if v >= 1 else "must be >= 1")

SCHED_QUEUE_DEPTH = register(
    "spark.rapids.tpu.sql.scheduler.queueDepth", 32,
    "Bound on queries WAITING in the scheduler's admission queue. "
    "Submissions beyond it are shed immediately with a typed "
    "QueryRejected error — the overload answer is an error the caller "
    "can retry with backoff, never an unbounded queue.",
    check=lambda v: None if v >= 0 else "must be >= 0")

SCHED_DEFAULT_PRIORITY = register(
    "spark.rapids.tpu.sql.scheduler.defaultPriority", 0,
    "Priority assigned when submit() passes none. Higher runs first; "
    "entries at equal priority are ordered weighted-fair by tenant "
    "virtual time (accumulated service / weight).")

SCHED_DEADLINE_MS = register(
    "spark.rapids.tpu.sql.scheduler.deadlineMs", 0,
    "Default per-query deadline in milliseconds (0 = none). Applies to "
    "scheduler submissions without an explicit deadline AND to "
    "synchronous collect() calls; expiry cancels the query "
    "cooperatively at the next batch boundary "
    "(QueryDeadlineExceeded), releasing semaphore permits, pipeline "
    "slots, and spill handles.",
    check=lambda v: None if v >= 0 else "must be >= 0")

ADMISSION_ENABLED = register(
    "spark.rapids.tpu.sql.scheduler.admission.enabled", True,
    "Predictive admission control (service/admission.py): the scheduler "
    "keeps an EWMA cost profile per statement fingerprint (runtime, "
    "device-byte footprint, spill events, fed from QueryStats at query "
    "completion) and packs concurrency against ESTIMATED memory instead "
    "of counting permits — a heavy recurring statement consumes more "
    "admission budget than a point lookup. Also enables deadline-aware "
    "queue shedding (entries whose remaining deadline is below their "
    "predicted runtime are shed typed 'doomed' instead of burning "
    "device time they cannot use) and the AIMD adaptive-concurrency "
    "controller. Queries without a fingerprint — in-process DataFrame "
    "submissions — and unknown fingerprints fall back to the static "
    "permit behavior exactly; false is the A/B kill switch restoring "
    "pre-admission behavior everywhere.")

ADMISSION_EWMA_ALPHA = register(
    "spark.rapids.tpu.sql.scheduler.admission.ewmaAlpha", 0.3,
    "EWMA smoothing factor for the per-fingerprint cost profiles "
    "(runtime, device bytes, spill events): profile = alpha * observed "
    "+ (1 - alpha) * profile. Higher adapts faster to drifting "
    "statement costs; lower resists one-off outliers.", conv=float,
    check=lambda v: None if 0.0 < v <= 1.0 else "must be in (0, 1]")

ADMISSION_DEVICE_BUDGET = register(
    "spark.rapids.tpu.sql.scheduler.admission.deviceBudgetBytes", 0,
    "Device-byte budget the predictive admission layer packs predicted "
    "query footprints into (0 = derive from the spill catalog's device "
    "budget). A query whose fingerprint predicts a footprint that does "
    "not fit beside the already-reserved in-flight predictions WAITS in "
    "the queue even when a semaphore permit is free — fewer concurrent "
    "heavy queries means fewer spill-degrades at equal maxConcurrent. "
    "At least one query is always admitted (no deadlock on a "
    "single over-budget statement).", conv=int,
    check=lambda v: None if v >= 0 else "must be >= 0")

ADMISSION_MAX_QUEUE_DELAY_MS = register(
    "spark.rapids.tpu.sql.scheduler.admission.maxQueueDelayMs", 0.0,
    "Submit-time overload shed: when the estimated queue drain time "
    "(queued entries x EWMA runtime / effective concurrency) exceeds "
    "this bound, submit() sheds immediately with a typed QueryRejected "
    "(reason 'overload') carrying a retry_after_ms hint, instead of "
    "queueing work that will rot past its deadline. 0 disables (the "
    "queueDepth bound still applies). The overload loadgen sets this "
    "to keep the queue honest at 5x offered load.", conv=float,
    check=lambda v: None if v >= 0 else "must be >= 0")

ADMISSION_AIMD_FLOOR = register(
    "spark.rapids.tpu.sql.scheduler.admission.aimd.floor", 1,
    "Lower bound on the AIMD controller's effective concurrency "
    "target. The controller never raises the target above "
    "scheduler.maxConcurrent nor lowers it below this floor.",
    check=lambda v: None if v >= 1 else "must be >= 1")

ADMISSION_AIMD_WINDOW = register(
    "spark.rapids.tpu.sql.scheduler.admission.aimd.window", 16,
    "Completions per AIMD adjustment window. Each window the "
    "controller inspects the observed spill-degrade rate (and p95 "
    "latency when aimd.latencyTargetMs is set): a bad window halves "
    "the effective concurrency target (multiplicative decrease, "
    "admission.aimd.backoff); a clean window raises it by one "
    "(additive increase) up to maxConcurrent — sustained overload "
    "converges to the goodput plateau instead of collapsing into "
    "spill thrash.",
    check=lambda v: None if v >= 1 else "must be >= 1")

ADMISSION_AIMD_BACKOFF = register(
    "spark.rapids.tpu.sql.scheduler.admission.aimd.backoff", 0.5,
    "Multiplicative-decrease factor applied to the AIMD concurrency "
    "target on a bad window (spill-degrade rate over "
    "aimd.spillDegradeThreshold, or p95 over aimd.latencyTargetMs).",
    conv=float,
    check=lambda v: None if 0.0 < v < 1.0 else "must be in (0, 1)")

ADMISSION_AIMD_SPILL_THRESHOLD = register(
    "spark.rapids.tpu.sql.scheduler.admission.aimd.spillDegradeThreshold",
    0.05,
    "Fraction of a window's completed queries that spilled device "
    "state above which the window counts as BAD and the AIMD target "
    "decreases multiplicatively. Spilling is the engine's graceful "
    "degradation, but a sustained spill rate means concurrency is "
    "packed past the device's working set — backing off restores the "
    "goodput plateau.", conv=float,
    check=lambda v: None if 0.0 <= v <= 1.0 else "must be in [0, 1]")

ADMISSION_AIMD_LATENCY_TARGET_MS = register(
    "spark.rapids.tpu.sql.scheduler.admission.aimd.latencyTargetMs", 0.0,
    "Optional p95 service-latency target for the AIMD controller: a "
    "window whose completed-query p95 exceeds it counts as bad "
    "(multiplicative decrease) even without spills. 0 disables the "
    "latency criterion (the spill-degrade criterion always applies).",
    conv=float, check=lambda v: None if v >= 0 else "must be >= 0")

BROWNOUT_ENABLED = register(
    "spark.rapids.tpu.sql.scheduler.brownout.enabled", True,
    "Brownout serving: when ALIVE cluster capacity (membership epoch "
    "events from parallel/dcn.py, or an explicit "
    "scheduler.on_membership call) falls below "
    "scheduler.brownout.enterFraction of the world, the scheduler "
    "enters a typed degraded mode — effective concurrency and tenant "
    "quotas scale to the surviving fraction, submissions below "
    "scheduler.brownout.shedBelowPriority shed typed (reason "
    "'brownout' + retry_after), and device-cache fills pause "
    "(serve-only) to preserve HBM headroom. Entered/exited with trace "
    "marks and snapshot visibility.")

BROWNOUT_ENTER_FRACTION = register(
    "spark.rapids.tpu.sql.scheduler.brownout.enterFraction", 0.75,
    "Alive-capacity fraction below which the scheduler enters "
    "brownout (and at-or-above which it exits): alive_ranks / "
    "world_size from the last membership event.",
    conv=float,
    check=lambda v: None if 0.0 < v <= 1.0 else "must be in (0, 1]")

BROWNOUT_SHED_BELOW_PRIORITY = register(
    "spark.rapids.tpu.sql.scheduler.brownout.shedBelowPriority", 0,
    "During brownout, submissions with priority strictly below this "
    "value shed immediately with the typed reason 'brownout' and a "
    "retry_after hint — surviving capacity serves the work that "
    "matters. The default (0, with defaultPriority 0) sheds only "
    "work explicitly submitted as low-priority.")

SERVER_RETRY_AFTER_MIN_MS = register(
    "spark.rapids.tpu.server.retryAfter.minMs", 50.0,
    "Floor on the server-computed retry_after_ms hint carried by "
    "typed overload sheds (REJECTED / QUOTA_EXCEEDED / DRAINING wire "
    "errors and GOAWAY frames). The hint is queue depth x predicted "
    "drain rate from the admission cost model, clamped to "
    "[minMs, maxMs]; clients back off at least this long so an empty "
    "queue cannot invite an instant-retry storm.", conv=float,
    check=lambda v: None if v >= 0 else "must be >= 0")

SERVER_RETRY_AFTER_MAX_MS = register(
    "spark.rapids.tpu.server.retryAfter.maxMs", 5000.0,
    "Ceiling on the server-computed retry_after_ms hint: even a deep "
    "queue of slow statements never tells a client to go away longer "
    "than this (the client's own jittered backoff layers on top).",
    conv=float, check=lambda v: None if v > 0 else "must be > 0")

DCN_HEARTBEAT_TIMEOUT = register(
    "spark.rapids.tpu.dcn.heartbeatTimeout", 15.0,
    "Seconds without a heartbeat before the DCN coordinator declares a "
    "rank dead (parallel/dcn.py). Service deployments on congested "
    "networks raise this to ride out GC/transfer pauses; lowering it "
    "surfaces real failures faster.", conv=float,
    check=lambda v: None if v > 0 else "must be > 0")

DCN_WAIT_TIMEOUT = register(
    "spark.rapids.tpu.dcn.waitTimeout", 120.0,
    "Seconds the DCN coordinator holds a barrier/allgather before "
    "failing it with PeerFailedError (parallel/dcn.py). Must exceed the "
    "longest legitimate inter-rank skew (e.g. one rank's cold XLA "
    "compile); bounds how long a lost peer can hang the world.",
    conv=float, check=lambda v: None if v > 0 else "must be > 0")

FUSION_ENABLED = register(
    "spark.rapids.tpu.sql.fusion.enabled", True,
    "Whole-query data-path fusion (plan/fusion.py): group chains of "
    "fusible operators between exchanges/sorts into regions that run "
    "as single pipeline stages, merge adjacent fused project/filter "
    "stages into ONE composed XLA program, and batch each region's "
    "size/stats host syncs (join build stats, dense-agg key stats, "
    "candidate-pair counts) into a single prologue fetch. false "
    "restores the exact per-operator dispatch-plus-materialize path — "
    "the byte-identical escape hatch the fusion-on/off differential "
    "tests pin.")

FUSION_MAX_OPS = register(
    "spark.rapids.tpu.sql.fusion.maxOps", 8,
    "Upper bound on operators grouped into one fused region. Oversized "
    "chains split at the member with the smallest observed self-time "
    "(the tracing spine's per-op profile) so the expensive ops stay "
    "co-resident in one region. Lower it when debugging to shrink the "
    "blast radius of a fused program; 1 keeps region accounting but "
    "never groups operators.",
    check=lambda v: None if v >= 1 else "must be >= 1")

PIPELINE_DEPTH = register(
    "spark.rapids.tpu.sql.pipeline.depth", 2,
    "Bounded depth of the async execution pipeline: scans and fused "
    "stages keep up to this many input batches staged ahead of the "
    "consumer (batch N+1's Arrow decode + host→device upload overlaps "
    "batch N's XLA dispatch), and collect resolves up to this many "
    "device→host fetches behind the dispatch front. 0 restores the "
    "fully serial pull loop (exact round-4 semantics; the debugging "
    "escape hatch). On the CPU backend the DEFAULT resolves to 0 "
    "(staging and compute share the same cores there, so overlap is "
    "contention, not latency hiding); setting the key explicitly "
    "always wins.",
    check=lambda v: None if v >= 0 else "must be >= 0")

HBM_POOL_FRACTION = register(
    "spark.rapids.tpu.memory.tpu.poolFraction", 0.9,
    "Fraction of free TPU HBM the arena manages for batch storage; "
    "allocations beyond it trigger spill-to-host.", startup_only=True)

HOST_SPILL_LIMIT = register(
    "spark.rapids.tpu.memory.host.spillStorageSize", 8 << 30,
    "Bytes of host memory for spilled device batches before they overflow "
    "to disk.")

SPILL_DIR = register(
    "spark.rapids.tpu.memory.spill.dir", "/tmp/srt_spill",
    "Directory for the disk spill tier.")

OOM_RETRY_ENABLED = register(
    "spark.rapids.tpu.memory.retry.enabled", True,
    "Catch device OOM inside operators, spill, and retry the work — "
    "splitting the input batch in half when a plain retry cannot fit.")

TEST_INJECT_OOM = register(
    "spark.rapids.tpu.test.injectRetryOOM", 0,
    "Test-only: force the next N device operations to raise a retry OOM so "
    "suites can prove operators survive and split correctly.", internal=True)

TEST_INJECT_SPLIT_OOM = register(
    "spark.rapids.tpu.test.injectSplitAndRetryOOM", 0,
    "Test-only: force the next N device operations to raise a "
    "split-and-retry OOM (RmmSpark.forceSplitAndRetryOOM analog).",
    internal=True)

SHUFFLE_MODE = register(
    "spark.rapids.tpu.shuffle.mode", "CACHE_ONLY",
    "Shuffle transport: CACHE_ONLY (partitions stay device-resident with "
    "spillable staging — fastest in one process), HOST (multithreaded "
    "host-staged shuffle: partition slices leave the device as compressed "
    "Arrow IPC frames, bounding HBM to one partition — "
    "RapidsShuffleThreadedWriter analog), ICI (XLA all-to-all collectives "
    "within a mesh for whole-stage-resident multi-chip execution).",
    check=_one_of("HOST", "ICI", "CACHE_ONLY"))

AGG_SKIP_PARTIAL_RATIO = register(
    "spark.rapids.tpu.sql.agg.skipPartialAggRatio", 0.3,
    "When a sampled first batch reduces to more than this fraction of its "
    "rows (high-cardinality group-by), the partial aggregate passes rows "
    "through to the exchange unreduced instead of sorting every batch — "
    "a partial sort pass only pays for itself above ~3x reduction "
    "(GpuHashAggregateExec skipAggPassReductionRatio analog). 1.0 "
    "disables skipping.", conv=float)

AUTO_BROADCAST_THRESHOLD = register(
    "spark.rapids.tpu.sql.autoBroadcastJoinThreshold", 256 * 1024 * 1024,
    "Estimated-size cutoff (bytes) under which the build side of a join is "
    "broadcast (materialized once, never shuffled) instead of hash "
    "partitioned; -1 disables auto selection (an explicit broadcast() "
    "hint still applies). spark.sql.autoBroadcastJoinThreshold analog. "
    "The default is far above Spark's 10MB: in a single process a "
    "broadcast build is just one materialization (which a shuffled join "
    "pays anyway) and feeds the dense direct-address kernel; lower this "
    "for DCN multi-host runs where the build all-gathers over the "
    "network.")

AGG_SINGLE_PROCESS_COMPLETE = register(
    "spark.rapids.tpu.sql.agg.singleProcessComplete", True,
    "Under shuffle.mode=CACHE_ONLY, plan grouped aggregations as one "
    "complete-mode pass instead of partial/exchange/final: with a single "
    "process the exchange colocates nothing and its staging + the "
    "partial-agg adaptivity sampling only add host round trips.")

AGG_REPARTITION_BUCKETS = register(
    "spark.rapids.tpu.sql.agg.repartitionBuckets", 64,
    "Hash-bucket count for the aggregate re-partition fallback "
    "(GpuMergeAggregateIterator analog): a final/complete aggregation "
    "whose merged output outgrows batchSizeRows splits into this many "
    "disjoint key buckets, each bounded at batchSizeRows rows (total "
    "group capacity = buckets x batchSizeRows; overflow raises).")

PY_WORKER_ISOLATION = register(
    "spark.rapids.tpu.python.worker.isolation", False,
    "Run each python UDF batch in a forked worker process so a crashing "
    "or hanging UDF raises PythonWorkerError instead of killing/wedging "
    "the engine (python/rapids/daemon.py + PythonWorkerSemaphore "
    "analog). Off by default: the fork + IPC round trip costs ~5-20 ms "
    "per batch.")

PY_WORKER_TIMEOUT = register(
    "spark.rapids.tpu.python.worker.timeout", 300.0,
    "Seconds an isolated python UDF batch may run before the worker is "
    "killed and PythonWorkerError raised.", conv=float)

AGG_DENSE_ENABLED = register(
    "spark.rapids.tpu.sql.agg.dense.enabled", True,
    "Enable the dense direct-address aggregation kernel (scatter into "
    "domain-sized accumulators) for single bounded-domain int/date "
    "group keys; the domain cap is join.denseDomainCap. Off = always "
    "use the sort-based kernel.")

AGG_DENSE_MAX_ACCUM = register(
    "spark.rapids.tpu.sql.agg.dense.maxAccumBytes", 1_500_000_000,
    "HBM budget for the multi-key dense aggregation's accumulators "
    "(primary-key domain x (residual min/max/validity channels + "
    "aggregate buffers)). Plans whose estimate exceeds it use the "
    "sort-based kernel.", conv=int)

ICI_OVERFLOW_RETRIES = register(
    "spark.rapids.tpu.shuffle.ici.overflowRetries", 2,
    "Transparent recovery attempts when an ICI fragment's fixed-capacity "
    "exchange bucket or join expansion overflows: each retry re-lowers "
    "the fragment with every static capacity scaled 4x and re-runs it "
    "(split-retry analog for static SPMD shapes). 0 = raise immediately.",
    conv=int)

PATH_REPLACEMENT = register(
    "spark.rapids.tpu.io.pathReplacementRules", "",
    "Comma list of 'prefix=>replacement' pairs applied to reader paths "
    "(first match wins): redirect remote object-store URIs to a local "
    "cache mount the way the reference rewrites s3:// to alluxio:// "
    "(AlluxioUtils.scala pathsToReplace analog). Empty disables.")

AQE_ENABLED = register(
    "spark.rapids.tpu.sql.aqe.enabled", True,
    "Adaptive re-planning at exchange boundaries: a shuffled join whose "
    "staged build input is ACTUALLY under autoBroadcastJoinThreshold "
    "flips to a broadcast join at runtime (GpuCustomShuffleReaderExec / "
    "runtime re-plan analog). Shuffle staging is reused either way.")

DPP_ENABLED = register(
    "spark.rapids.tpu.sql.dpp.enabled", True,
    "Dynamic partition pruning: after a broadcast join's build side "
    "materializes, push its key range (and, when the distinct count is "
    "small, the exact key list) into the probe-side scan as runtime "
    "predicates for file/row-group pruning. GpuSubqueryBroadcastExec / "
    "GpuDynamicPruningExpression analog.")

DPP_MAX_IN_KEYS = register(
    "spark.rapids.tpu.sql.dpp.maxInKeys", 10_000,
    "Largest distinct build-key count pushed as an exact IN-list runtime "
    "predicate; above it only the [min, max] range is pushed.")

DENSE_JOIN_MIN_PROBE = register(
    "spark.rapids.tpu.join.denseMinProbeRows", 16384,
    "Smallest ESTIMATED probe-side row count for which a broadcast join "
    "engages the dense direct-address machinery (build-key stats fetch, "
    "dense table, dynamic partition pruning). Below it the sorted "
    "kernel runs without the stats round trip: a blocking fetch stalls "
    "the dispatch front, which a tiny probe never earns back. 0 always "
    "engages.")

DENSE_JOIN_DOMAIN_CAP = register(
    "spark.rapids.tpu.join.denseDomainCap", 1 << 26,
    "Largest key domain (max_key - min_key + 1) for which the dense "
    "direct-address kernels engage — the TPU-native replacement for "
    "cuDF's device hash table (GpuHashJoin.scala:104): broadcast joins "
    "build an int32 key->row table (one HBM gather per probe row), and "
    "single-int-key complete-mode aggregations scatter into domain-sized "
    "accumulators (one per buffer column: budget ~cap x 8B x buffers). "
    "Above the cap the sort-based kernels run. 0 disables both.")

ICI_DEVICES = register(
    "spark.rapids.tpu.shuffle.ici.devices", 0,
    "Number of mesh devices for ICI shuffle (0 = all visible devices). The "
    "session builds a 1-D jax.sharding.Mesh over them; use "
    "Session.set_mesh() for custom topologies.")

ICI_BUCKET_ROWS = register(
    "spark.rapids.tpu.shuffle.ici.bucketRows", 0,
    "Per-destination send-bucket rows for an ICI all_to_all exchange "
    "(0 = auto: the capacity-ladder rung over the rows counted for the "
    "fullest bucket, read once per level of exchanges, which can never "
    "overflow). An explicit value is held to; rows counted past it are "
    "detected and raised, never dropped.")

ICI_JOIN_OUT_ROWS = register(
    "spark.rapids.tpu.shuffle.ici.joinOutputRows", 0,
    "Static per-device output capacity of an ICI shuffled join expansion "
    "(0 = auto: probe+build shard capacities). Overflow is detected and "
    "raised, never dropped.")

ICI_FALLBACK = register(
    "spark.rapids.tpu.shuffle.ici.fallback", False,
    "When true, exchanges that cannot be lowered onto the mesh run on the "
    "single-process CACHE_ONLY path (with a warning) instead of failing "
    "the query.", conv=_to_bool)

SHUFFLE_PARTITIONS = register(
    "spark.rapids.tpu.sql.shuffle.partitions", 8,
    "Default number of shuffle partitions for exchanges. On one chip a "
    "partition exists for memory decomposition, not parallelism, and every "
    "partition costs fixed per-pass device dispatches — keep it low unless "
    "data outgrows HBM.")

EXCHANGE_ENABLED = register(
    "spark.rapids.tpu.sql.exchange.enabled", True,
    "Plan grouped aggregations as partial→exchange→final and equi-joins "
    "over hash-partitioned sides (the distributed dataflow, realized "
    "in-process on one chip). Disable to run single-stream complete-mode "
    "operators.")

SHUFFLE_COMPRESS = register(
    "spark.rapids.tpu.shuffle.compress", True,
    "Compress host-staged shuffle payloads (lz4 via the native host library "
    "when built, else zlib).")

READER_THREADS = register(
    "spark.rapids.tpu.sql.multiThreadedRead.numThreads", 8,
    "Threads prefetching and parsing input files to host memory while the "
    "device computes (multi-file cloud reader analog). 0 disables prefetch.")

SCAN_EXACT_FILTER = register(
    "spark.rapids.tpu.sql.scan.exactFilterPushdown", True,
    "Apply fully-pushable filter conjuncts on host during the scan (Arrow "
    "C++ kernels) so filtered-out rows never pay the host→HBM upload. The "
    "device filter still evaluates the complete condition; this is the "
    "late-materialization analog of the reference pushing predicates into "
    "the device decode.")

FILE_CACHE_ENABLED = register(
    "spark.rapids.tpu.sql.fileCache.enabled", False,
    "Cache decoded Arrow tables of scanned files in host memory (keyed by "
    "path+mtime+columns+row-groups) so repeated scans skip the parquet "
    "decode. Analog of the reference's FileCache (filecache.md). Host "
    "tier only: uploaded batches stay on the device under "
    "sql.cache.enabled + sql.cache.scan.enabled.")

FILE_CACHE_MAX_BYTES = register(
    "spark.rapids.tpu.sql.fileCache.maxBytes", 4 << 30,
    "Byte budget for the decoded-file cache; least-recently-used files are "
    "evicted beyond it.")

CACHE_ENABLED = register(
    "spark.rapids.tpu.sql.cache.enabled", False,
    "Master switch for the CROSS-QUERY device cache "
    "(spark_rapids_tpu/cache/): uploaded scan batches and materialized "
    "broadcast build sides stay HBM-resident across queries, keyed by "
    "source fingerprint (files+mtime+size, projection, pushed filters) "
    "so a write invalidates. Cached bytes are registered with the spill "
    "catalog at a priority BELOW live query state — memory pressure "
    "demotes cold cache entries to host/disk before touching a running "
    "query, never OOMs it. The concurrent-service replay (bench "
    "SRT_BENCH_CONCURRENCY) is the headline beneficiary: tenants "
    "replaying the same tables skip decode, H2D upload, and broadcast "
    "hash-build entirely.")

CACHE_MAX_BYTES = register(
    "spark.rapids.tpu.sql.cache.maxBytes", 2 << 30,
    "Byte budget for the cross-query cache (device + host-string bytes "
    "of cached batches). Least-recently-used entries not held by a "
    "running query are dropped beyond it; entries a query currently "
    "holds are never dropped (refcounted).")

CACHE_SCAN_ENABLED = register(
    "spark.rapids.tpu.sql.cache.scan.enabled", True,
    "With sql.cache.enabled: cache uploaded scan output per (source "
    "fingerprint, projection, pushed predicates). A hit skips parquet "
    "decode AND the host->HBM upload; a scan projecting a SUBSET of a "
    "cached entry's columns slices the cached batches instead of "
    "re-uploading (partial hit).")

CACHE_BROADCAST_ENABLED = register(
    "spark.rapids.tpu.sql.cache.broadcast.enabled", True,
    "With sql.cache.enabled: share materialized broadcast build sides "
    "across queries via refcounted handles, keyed by the build "
    "subtree's structural fingerprint (scan tokens + stage expression "
    "fingerprints). Cached builds carry their probed dense-join key "
    "stats, so a reuse hit also skips the build's blocking stats "
    "fetches (two per join).")

CACHE_TTL_MS = register(
    "spark.rapids.tpu.sql.cache.ttlMs", 0,
    "Milliseconds a cross-query cache entry stays servable (0 = no "
    "TTL). Source-fingerprint keys already invalidate on file "
    "mtime/size changes and the write paths invalidate eagerly; the "
    "TTL bounds staleness for external writers the engine cannot see.")

MAX_READER_BATCH_BYTES = register(
    "spark.rapids.tpu.sql.reader.batchSizeBytes", 512 << 20,
    "Soft cap on bytes of file data decoded into a single scan batch.")

HASH_SUBPARTITIONS = register(
    "spark.rapids.tpu.sql.join.subPartitions", 16,
    "Fan-out used to re-partition an OVERSIZED shuffled-join partition "
    "pair (combined rows above sql.batchSizeRows) by a second independent "
    "key hash before joining (GpuSubPartitionHashJoin analog).")

ANSI_ENABLED = register(
    "spark.rapids.tpu.sql.ansi.enabled", False,
    "ANSI mode: arithmetic overflow and invalid casts raise instead of "
    "returning null.")

CPU_FALLBACK_ENABLED = register(
    "spark.rapids.tpu.sql.fallback.enabled", True,
    "Execute unsupported operators on the CPU (Arrow/pandas kernels) instead "
    "of failing the query.")

METRICS_LEVEL = register(
    "spark.rapids.tpu.sql.metrics.level", "MODERATE",
    "Operator metric collection level: ESSENTIAL, MODERATE, DEBUG.",
    check=_one_of("ESSENTIAL", "MODERATE", "DEBUG"))

TRACE_ENABLED = register(
    "spark.rapids.tpu.sql.trace.enabled", False,
    "Record a structured query trace: one span per physical plan "
    "operator (mirroring the plan tree) with child phase spans for "
    "decode, H2D staging, dispatch, pipeline wait, and D2H fetch, plus "
    "compile and shuffle events — the attribution spine behind "
    "df.explain('profiled'), Session.last_trace(), and the Chrome-trace "
    "export (tools/trace_report.py). Off by default; the disabled path "
    "is a single context-variable read per event site.")

TRACE_DIR = register(
    "spark.rapids.tpu.sql.trace.dir", "",
    "When set (and sql.trace.enabled=true), write one Chrome-trace-event "
    "JSON file per executed query into this directory (loads in Perfetto "
    "or chrome://tracing; bench.py points it at SRT_BENCH_TRACE_DIR). "
    "Empty disables the auto-dump — traces stay available in-process via "
    "Session.last_trace().")

TRACE_MAX_EVENTS = register(
    "spark.rapids.tpu.sql.trace.maxEvents", 100_000,
    "Hard cap on recorded trace events per query; events beyond it are "
    "counted (otherData.dropped_events in the export) but not stored, "
    "bounding trace memory for long streaming queries.", conv=int)

RECORDER_ENABLED = register(
    "spark.rapids.tpu.recorder.enabled", True,
    "Performance flight recorder: run tracing always-on and offer "
    "every completed query's span tree to a bounded per-process ring "
    "(utils/recorder.py). Retention keeps the interesting tail — SLO "
    "violations, non-ok outcomes, top-k slowest per statement "
    "fingerprint, first-seen fingerprints — and drops the boring "
    "median (counted in recorder_dropped_total). Retained traces are "
    "listed in /snapshot and /debug/slow and dump to sql.trace.dir "
    "when set. Span overhead is the same <2.5% the tracer already "
    "pays; the ring bounds the memory.")

RECORDER_MAX_QUERIES = register(
    "spark.rapids.tpu.recorder.maxQueries", 48,
    "Retained query traces the flight-recorder ring holds before "
    "evicting oldest-first (recorder_dropped_total{reason=evicted}).",
    conv=int, check=lambda v: None if v >= 1 else "must be >= 1")

RECORDER_MAX_BYTES = register(
    "spark.rapids.tpu.recorder.maxBytes", 32 << 20,
    "Approximate byte budget for the flight-recorder ring (estimated "
    "per-event, not deep-measured); oldest captures evict until under "
    "budget, though the newest capture always survives.",
    conv=int, check=lambda v: None if v >= 1 else "must be >= 1")

TEST_VALIDATE_EXECS = register(
    "spark.rapids.tpu.test.validateExecsOnTpu", False,
    "Test-only: fail if any operator in the plan falls back to CPU.",
    internal=True)

FAULTS_RECOVERY_ENABLED = register(
    "spark.rapids.tpu.faults.recovery.enabled", True,
    "Master switch for transient-failure recovery (spark_rapids_tpu/"
    "faults/): I/O reads, shuffle-fragment pulls, and DCN traffic retry "
    "with exponential backoff + jitter; repeated device-op failure "
    "degrades the batch to the CPU path. When false every transient "
    "fault immediately fails the query with a typed QueryFaulted "
    "carrying the fault history (the fail-fast debugging mode).")

FAULTS_MAX_RETRIES = register(
    "spark.rapids.tpu.faults.maxRetries", 3,
    "Attempts per faulting call site before transient_retry gives up "
    "with QueryFaulted. Each retry also draws down the per-query "
    "faults.retryBudget.",
    check=lambda v: None if v >= 0 else "must be >= 0")

FAULTS_RETRY_BUDGET = register(
    "spark.rapids.tpu.faults.retryBudget", 64,
    "Per-query cap on transient retries across ALL fault points (the "
    "storm brake: a query riding a failing disk or a flapping peer must "
    "fail typed, not spin forever). Exhaustion raises QueryFaulted with "
    "the accumulated fault history.",
    check=lambda v: None if v >= 0 else "must be >= 0")

FAULTS_BACKOFF_BASE_MS = register(
    "spark.rapids.tpu.faults.backoff.baseMs", 25.0,
    "First-retry backoff in milliseconds; attempt N sleeps "
    "min(maxMs, baseMs * multiplier^(N-1)) scaled by a seeded jitter "
    "factor in [0.5, 1.0]. Also paces DCN connect retries and the "
    "coordinator's barrier re-check cadence (parallel/dcn.py).",
    conv=float, check=lambda v: None if v >= 0 else "must be >= 0")

FAULTS_BACKOFF_MAX_MS = register(
    "spark.rapids.tpu.faults.backoff.maxMs", 2000.0,
    "Ceiling on a single transient-retry backoff sleep in milliseconds.",
    conv=float, check=lambda v: None if v > 0 else "must be > 0")

FAULTS_BACKOFF_MULTIPLIER = register(
    "spark.rapids.tpu.faults.backoff.multiplier", 2.0,
    "Exponential growth factor between consecutive backoff sleeps.",
    conv=float, check=lambda v: None if v >= 1 else "must be >= 1")

FAULTS_DEVICE_RETRIES = register(
    "spark.rapids.tpu.faults.device.retries", 2,
    "Re-dispatch attempts for a device op failing with a transient "
    "(non-OOM) runtime error before the batch degrades to the CPU "
    "fallback path (faults.degrade.enabled) or the query fails typed. "
    "OOM keeps its own spill-and-retry protocol (memory/retry.py).",
    check=lambda v: None if v >= 0 else "must be >= 0")

FAULTS_DEGRADE_ENABLED = register(
    "spark.rapids.tpu.faults.degrade.enabled", True,
    "After device-op retries exhaust, run that batch through the "
    "operator's cpu/ fallback instead of failing the query — marked "
    "degraded:cpu in the trace and counted in QueryStats."
    " Disable to surface persistent device faults as QueryFaulted.")

FAULTS_INJECT_SCHEDULE = register(
    "spark.rapids.tpu.faults.inject.schedule", "",
    "Deterministic fault-injection schedule: comma list of "
    "'point:N[:K]' entries — fail invocations N..N+K-1 (1-based) at "
    "the named point (io.read, io.write, shuffle.fragment, "
    "dcn.heartbeat, device.op, cache.lookup, dcn.peer_kill, plus the "
    "gray points shuffle.corrupt, spill.corrupt, cache.corrupt, "
    "device.hang, dcn.slow_peer — gray points corrupt/wedge/delay "
    "instead of raising — and the network points dcn.partition "
    "(drop the Nth fabric-checked DCN send), dcn.net.dup and "
    "dcn.net.reorder (duplicate / stale-replay the Nth delivery at a "
    "DCN serve loop)). Counters "
    "reset per query. Empty disables. The chaos differential suite "
    "proves results under a schedule equal the fault-free run; "
    "dcn.peer_kill:N kills THIS rank at its Nth shuffle op "
    "(dcn.kill.mode selects silent heartbeat stop vs hard exit), "
    "driving the killed-peer differential.")

FAULTS_INJECT_RATE = register(
    "spark.rapids.tpu.faults.inject.rate", 0.0,
    "Probabilistic chaos-injection rate in [0, 1): every invocation at "
    "the selected points (faults.inject.points) fails with this "
    "probability, drawn from a generator seeded by faults.inject.seed "
    "so runs replay exactly. bench.py exposes it as "
    "SRT_BENCH_FAULT_RATE.", conv=float,
    check=lambda v: None if 0.0 <= v < 1.0 else "must be in [0, 1)")

FAULTS_INJECT_POINTS = register(
    "spark.rapids.tpu.faults.inject.points", "",
    "Comma list restricting rate-based injection to these points "
    "(empty = every registered point, gray ones included). "
    "Deterministic schedule entries name their points explicitly.")

FAULTS_INJECT_SEED = register(
    "spark.rapids.tpu.faults.inject.seed", 0,
    "Seed for the injection RNG (probabilistic rate draws AND the "
    "retry backoff jitter), making chaos runs reproducible.")

FAULTS_INJECT_FINGERPRINT = register(
    "spark.rapids.tpu.faults.inject.fingerprint", "",
    "Statement fingerprint (cache/keys.statement_fingerprint) that "
    "SCOPES injection: when set, schedule and rate injection fire — "
    "and deterministic invocation counters advance — only inside "
    "queries carrying this fingerprint, so a poison-query scenario "
    "(tools/loadgen.py --poison, the containment tests) targets one "
    "statement in a mixed workload without touching healthy queries. "
    "Empty = inject everywhere (the pre-existing behavior).")

FAULTS_INTEGRITY_ENABLED = register(
    "spark.rapids.tpu.faults.integrity.enabled", True,
    "Verify the checksum stamped on every durable byte path — spill "
    "files, host-shuffle frames and durable map output, DCN fragment "
    "transfers, and atomic-writer output sidecars (faults/integrity"
    ".py). A mismatch is a typed IntegrityFault converted into the "
    "existing recovery vocabulary: corrupt shuffle fragment -> re-pull "
    "from durable map output, corrupt cache entry -> drop-and-miss, "
    "corrupt spill file backing live state -> QueryFaulted "
    "(resubmittable). Stamping itself is always on (one crc32 over "
    "bytes already in motion); this gates only verification.")

FAULTS_WATCHDOG_ENABLED = register(
    "spark.rapids.tpu.faults.watchdog.enabled", True,
    "Per-query progress watchdog for scheduler-run queries (service/"
    "watchdog.py): fed by the batch-pull checkpoints every operator "
    "already passes, it escalates a query making no progress for "
    "faults.watchdog.stallMs — stack-dump mark in the trace, then "
    "cooperative cancel, then faulted(resubmittable) with the running "
    "slot and semaphore permit reclaimed — so a hung D2H fetch or "
    "wedged DCN wait can never strand a scheduler permit forever.")

FAULTS_WATCHDOG_STALL_MS = register(
    "spark.rapids.tpu.faults.watchdog.stallMs", 30000.0,
    "How long an admitted query may go without producing a batch (or "
    "passing any batch-pull checkpoint) before the watchdog declares "
    "it stalled and escalates. The floor is one slow-but-honest batch; "
    "detection lands within stallMs + one watchdog poll.",
    conv=float, check=lambda v: None if v > 0 else "must be > 0")

FAULTS_HEDGE_ENABLED = register(
    "spark.rapids.tpu.faults.hedge.enabled", True,
    "Hedge DCN shuffle-fragment fetches against slow peers (parallel/"
    "dcn.py): per-peer response times are tracked, a peer whose "
    "replies exceed faults.hedge.quantileMs is declared SLOW (distinct "
    "from declared-dead), and a fetch still pending at the hedge "
    "horizon starts a parallel read of the peer's durable map output — "
    "first result wins, the loser is abandoned (fragments_hedged).")

FAULTS_HEDGE_QUANTILE_MS = register(
    "spark.rapids.tpu.faults.hedge.quantileMs", 1000.0,
    "Hedge horizon in milliseconds: a remote fragment fetch still "
    "pending after this long races a durable-map-output read; a peer "
    "answering slower than this is declared slow and subsequent "
    "fetches hedge immediately. Tune toward a high quantile of the "
    "observed fetch latency (the classic tail-at-scale hedge).",
    conv=float, check=lambda v: None if v > 0 else "must be > 0")

FAULTS_DCN_GC_ORPHAN_FRAMES_MS = register(
    "spark.rapids.tpu.faults.dcn.gcOrphanFramesMs", 600000.0,
    "Age threshold for sweeping orphaned shuffle frame directories "
    "from the spill dir when a new DCN shuffle starts. Killed ranks "
    "deliberately leave their frame files behind (they are the durable "
    "map output survivors re-pull), so chaos runs accumulate them; "
    "the sweep removes shuffle-* dirs untouched for this long. "
    "0 disables.", conv=float,
    check=lambda v: None if v >= 0 else "must be >= 0")

FAULTS_RESUBMIT_MAX = register(
    "spark.rapids.tpu.faults.resubmit.max", 1,
    "Times the scheduler automatically RESUBMITS a query that failed "
    "permanent-at-this-placement (QueryFaulted with resubmittable=True "
    "— a DCN peer the coordinator declared dead, a lost coordinator). "
    "The faulted attempt's trace finishes with a 'resubmitted' status "
    "linked to the retry; the retry re-enters the admission queue and "
    "runs against the surviving membership. 0 disables resubmission "
    "(the typed QueryFaulted surfaces to the caller on the first "
    "permanent failure).",
    check=lambda v: None if v >= 0 else "must be >= 0")

FAULTS_BREAKER_ENABLED = register(
    "spark.rapids.tpu.faults.breaker.enabled", True,
    "Per-fingerprint circuit breakers (service/breaker.py): CHARGEABLE "
    "completion outcomes (watchdog stall/force-reclaim, device-guard "
    "exhaustion, OOM past spill) trip a statement fingerprint's breaker "
    "after faults.breaker.strikes strikes; an open breaker sheds that "
    "statement at admission with the typed wire code QUARANTINED + "
    "retry_after, blocks further resubmission, and half-opens into one "
    "sandboxed canary after faults.breaker.openMs. VICTIM outcomes "
    "(peer loss, coordinator failover, drain, integrity re-pull) never "
    "count. Disabling restores the contain-nothing behavior (every "
    "poison attempt re-runs at full cost).")

FAULTS_BREAKER_STRIKES = register(
    "spark.rapids.tpu.faults.breaker.strikes", 2,
    "Chargeable strikes before a statement fingerprint's breaker opens "
    "(the two-strike culprit rule: a poison query stops being "
    "resubmitted after it kills its second worker). A successful run "
    "resets the count — poison is deterministic failure, not a bad "
    "day.",
    check=lambda v: None if v >= 1 else "must be >= 1")

FAULTS_BREAKER_OPEN_MS = register(
    "spark.rapids.tpu.faults.breaker.openMs", 10000.0,
    "Quarantine window after a breaker opens: admissions of the "
    "fingerprint shed typed (QUARANTINED, retry_after = the remaining "
    "window) until it elapses, then ONE canary runs under the sandbox "
    "profile. Each re-trip doubles the window up to "
    "faults.breaker.openMaxMs.")

FAULTS_BREAKER_OPEN_MAX_MS = register(
    "spark.rapids.tpu.faults.breaker.openMaxMs", 300000.0,
    "Cap on the doubling quarantine window of a repeatedly re-tripped "
    "breaker (a statement that fails its canary every time stays "
    "quarantined, re-probed at most this often).")

FAULTS_BREAKER_CANARY_DEADLINE_MS = register(
    "spark.rapids.tpu.faults.breaker.canary.deadlineMs", 10000.0,
    "Tightened deadline for the half-open canary run (the sandbox "
    "profile also forces pipeline depth 0 and allows cpu/ "
    "degradation): the probe must prove health cheaply, not burn "
    "another full watchdog window. 0 = the canary keeps the "
    "caller's deadline.")

FAULTS_BREAKER_BUNDLE_DIR = register(
    "spark.rapids.tpu.faults.breaker.bundle.dir", "",
    "Directory for quarantine diagnosis bundles (breaker state, typed "
    "fault lineage, the finished trace with watchdog stall stacks, the "
    "wire spec, conf overrides — rendered by tools/diagnose.py). "
    "Empty = <memory.spill.dir>/diagnosis.")

FAULTS_BREAKER_BUNDLE_MAX = register(
    "spark.rapids.tpu.faults.breaker.bundle.max", 16,
    "Bounded retention for diagnosis bundles: beyond this many bundle "
    "directories the oldest are deleted (a crash-looping statement "
    "must not fill the disk with postmortems).",
    check=lambda v: None if v >= 1 else "must be >= 1")

DCN_EPOCH_FENCING = register(
    "spark.rapids.tpu.dcn.epoch.fencing", True,
    "Fence DCN control frames and peer fetches with the cluster epoch: "
    "the coordinator bumps the epoch whenever it declares a rank dead "
    "or admits a restarted rank under a fresh incarnation, and rejects "
    "stale-epoch/stale-incarnation messages so a zombie rank cannot "
    "resurrect with stale shuffle state (parallel/dcn.py). Live ranks "
    "resync transparently from the rejection reply; disabling restores "
    "the pre-epoch wire behavior (debugging escape hatch).")

DCN_COORDINATOR_STANDBY = register(
    "spark.rapids.tpu.dcn.coordinator.standby", True,
    "Stream the coordinator's membership journal (epoch, incarnations, "
    "declared-dead set, replayable snapshots of recently completed "
    "barriers/gathers — including the shuffle commit gathers that carry "
    "every rank's durable map-output dir) to a STANDBY on the "
    "next-lowest alive rank, write-ahead of collective replies, and "
    "fail over to that deterministic successor on coordinator loss: "
    "survivors re-dial the standby's peer server (which serves control "
    "ops from the restored journal after promoting), resync the epoch, "
    "and re-send the in-flight collective — completed tags replay "
    "byte-identically. Coordinator loss is then permanent "
    "(CoordinatorUnrecoverableError, resubmittable) only when no "
    "successor exists (world <= 1 survivor) or takeover never "
    "completes. Disabling restores the coordinator-as-single-point-of-"
    "failure behavior (debugging escape hatch).")

DCN_KILL_MODE = register(
    "spark.rapids.tpu.dcn.kill.mode", "silent",
    "How the dcn.peer_kill injection point kills this rank (chaos "
    "testing only): 'silent' stops heartbeating and FREEZES the peer "
    "server (sockets stay open, requests are never answered) so death "
    "is only visible through failure detection — the worst case; "
    "'hard' exits the process immediately (os._exit), the "
    "crashed-executor shape. Meaningful only with a dcn.peer_kill "
    "entry armed in faults.inject.schedule.",
    check=lambda v: None if v in ("silent", "hard")
    else "must be 'silent' or 'hard'")

DCN_FLAP_THRESHOLD = register(
    "spark.rapids.tpu.dcn.flap.threshold", 3,
    "Re-registrations of one rank within dcn.flap.windowS before the "
    "coordinator starts DAMPING it: further rejoin attempts get a "
    "typed deferral reply (deferred=true + retry_after_ms on an "
    "exponential curve) instead of an epoch bump, so a crash-looping "
    "host cannot drag the fleet through an epoch-churn/orphan-adoption "
    "storm per lap. 0 disables damping.",
    check=lambda v: None if v >= 0 else "must be >= 0")

DCN_FLAP_WINDOW_S = register(
    "spark.rapids.tpu.dcn.flap.windowS", 60.0,
    "Rolling window for the flap counter: a rank whose last "
    "re-registration is older than this rejoins with a clean history "
    "(an occasional planned restart is not a flap).")

DCN_FLAP_BASE_MS = register(
    "spark.rapids.tpu.dcn.flap.baseMs", 1000.0,
    "First rejoin-deferral delay once a rank crosses "
    "dcn.flap.threshold; each further flap doubles it up to "
    "dcn.flap.maxMs. The deferral state rides the membership journal, "
    "so damping survives a coordinator failover.")

DCN_FLAP_MAX_MS = register(
    "spark.rapids.tpu.dcn.flap.maxMs", 60000.0,
    "Cap on the exponential rejoin-deferral delay of a flapping rank.")

DCN_SUSPECT_STRIKES = register(
    "spark.rapids.tpu.dcn.suspect.strikes", 2,
    "Consecutive missed heartbeat windows (each dcn.heartbeatTimeout "
    "long) before the coordinator DECLARES a silent rank dead. The "
    "first miss only SUSPECTS the rank (peer:suspected mark, visible "
    "in Coordinator.suspected()); any contact within the next window "
    "clears the suspicion — so injected link delay and real congestion "
    "stop causing spurious death declarations and the epoch churn that "
    "follows them. 1 restores declare-on-first-timeout.",
    check=lambda v: None if v >= 1 else "must be >= 1")

DCN_QUORUM_ENABLED = register(
    "spark.rapids.tpu.dcn.quorum.enabled", True,
    "Quorum-fence membership decisions against network partitions "
    "(world >= 3; parallel/dcn.py): a rank may only promote/adopt a "
    "successor coordinator after connectivity votes (the 'vote' DCN "
    "op, served by every peer server) from a strict majority of the "
    "last-agreed alive set confirm the coordinator is unreachable — "
    "minority-side ranks park with a typed QuorumLostError "
    "(resubmittable) instead of electing a second coordinator; and the "
    "coordinator itself stops declaring deaths (zero epoch bumps) "
    "while the ranks still heartbeating it are a minority. Generation "
    "fencing makes a healed stale coordinator abdicate to the higher "
    "generation. Disabling restores the fail-stop-biased failover "
    "(debugging escape hatch; 2-rank groups are always fail-stop — no "
    "quorum exists at world 2).")

DCN_QUORUM_WINDOW_MS = register(
    "spark.rapids.tpu.dcn.quorum.windowMs", 4000.0,
    "How long a rank polls connectivity votes for a strict majority "
    "before deciding it is on the minority side of a partition and "
    "parking typed (QuorumLostError). Voters answer from their own "
    "recent coordinator-contact age, so the window must cover at least "
    "one heartbeat interval plus the liveness horizon of the slowest "
    "voter.")

FAULTS_NET_PARTITION = register(
    "spark.rapids.tpu.faults.net.partition", "",
    "Standing link cuts for the DCN fault fabric "
    "(faults/netfabric.py), comma list: 'a>b' drops frames from rank a "
    "to rank b (asymmetric — b>a still flows), 'a-b' cuts both "
    "directions, '0+1|2' cuts every link between rank groups {0,1} and "
    "{2} ('*' = every other rank). A cut link refuses sends with a "
    "typed LinkPartitionedError so retry/failover/durable-re-pull "
    "machinery engages as for a real dead link. Empty disables.")

FAULTS_NET_DELAY_MS = register(
    "spark.rapids.tpu.faults.net.delayMs", "",
    "Added one-way link latency for the DCN fault fabric, comma list: "
    "'a>b:ms', 'a-b:ms', or '*:ms'. Composes with dcn.suspect.strikes "
    "— delay under the strike horizon must not cause death "
    "declarations. Empty disables.")

FAULTS_NET_DUP_RATE = register(
    "spark.rapids.tpu.faults.net.dup.rate", 0.0,
    "Probability a frame arriving at a DCN serve loop (coordinator or "
    "peer server) is DELIVERED TWICE, drawn from a generator seeded by "
    "faults.net.seed. The per-request dedup journal must make the "
    "second delivery a byte-identical replay (no double-applied "
    "registers, no double-counted stats).",
    check=lambda v: None if 0.0 <= v <= 1.0 else "must be in [0, 1]")

FAULTS_NET_REORDER_RATE = register(
    "spark.rapids.tpu.faults.net.reorder.rate", 0.0,
    "Probability a DCN serve loop re-delivers the connection's "
    "PREVIOUS frame ahead of the current one (the stale-duplicate-"
    "arrives-late reordering shape), seeded by faults.net.seed; the "
    "dedup journal must absorb the stale replay.",
    check=lambda v: None if 0.0 <= v <= 1.0 else "must be in [0, 1]")

FAULTS_NET_SEED = register(
    "spark.rapids.tpu.faults.net.seed", 0,
    "Seed for the fabric's dup/reorder draws, so network chaos runs "
    "replay exactly (identical re-arms preserve the RNG stream, like "
    "faults.inject.seed).")

FAULTS_NET_AFTER_OPS = register(
    "spark.rapids.tpu.faults.net.afterOps", 0,
    "Engage the standing faults.net.* program only after this rank has "
    "counted this many shuffle ops (the deterministic mid-query "
    "trigger, mirroring dcn.peer_kill's 'after N ops' shape). 0 "
    "engages immediately.",
    check=lambda v: None if v >= 0 else "must be >= 0")


SERVER_HOST = register(
    "spark.rapids.tpu.server.host", "127.0.0.1",
    "Bind address for the network SQL front door (server/endpoint.py): a "
    "length-prefixed, crc-stamped Arrow IPC streaming endpoint in front "
    "of the query scheduler (Arrow Flight SQL analog). Loopback by "
    "default; bind 0.0.0.0 only behind real network auth.")

SERVER_PORT = register(
    "spark.rapids.tpu.server.port", 0,
    "TCP port for the SQL front door. 0 picks an ephemeral port "
    "(SqlFrontDoor.port reports it — the test/loadgen mode).",
    check=lambda v: None if 0 <= v < 65536 else "must be in [0, 65536)")

SERVER_MAX_CONNECTIONS = register(
    "spark.rapids.tpu.server.maxConnections", 32,
    "Concurrent client connections the front door serves. Connections "
    "beyond it are answered with a typed REJECTED wire error and closed "
    "— the same shed-don't-queue overload contract as the scheduler's "
    "admission queue.",
    check=lambda v: None if v >= 1 else "must be >= 1")

SERVER_AUTH_TOKEN = register(
    "spark.rapids.tpu.server.authToken", "",
    "Shared-secret auth hook for the front door: when set, a client's "
    "HELLO must present the same token or the connection fails typed "
    "(UNAUTHENTICATED) and closes. Empty = open (loopback/dev mode). "
    "The hook is deliberately minimal — per-tenant identity rides the "
    "HELLO tenant field onto the scheduler's weighted-fair tenants.")

SERVER_TENANT_QUOTAS = register(
    "spark.rapids.tpu.server.tenantQuotas", "",
    "Comma list of 'tenant=N' caps on a tenant's in-flight wire queries "
    "('*=N' sets the default for unlisted tenants; empty/0 = unlimited). "
    "A query over quota is shed at the protocol layer with a typed "
    "QUOTA_EXCEEDED wire error BEFORE touching the scheduler — overload "
    "degrades to a retryable error the client sees immediately, never a "
    "hang.")

SERVER_IDLE_TIMEOUT = register(
    "spark.rapids.tpu.server.idleTimeout", 300.0,
    "Seconds a connection may sit idle (no request frame) before the "
    "server closes it — the bound on every server-side socket recv, so "
    "a wedged or vanished client can never pin a connection slot "
    "forever.", conv=float,
    check=lambda v: None if v > 0 else "must be > 0")

SERVER_PREPARED_ENABLED = register(
    "spark.rapids.tpu.server.preparedCache.enabled", True,
    "Enable the prepared-statement plan cache (server/prepared.py): "
    "PREPARE parses the query spec and runs logical+physical planning "
    "ONCE; EXECUTE re-runs the cached physical tree with freshly bound "
    "parameter values (exprs.ParamExpr) — the single biggest lever for "
    "small interactive queries, which otherwise pay full planning per "
    "submit. Disabled, PREPARE still works but replans per execution "
    "(the A/B debugging mode).")

SERVER_PREPARED_MAX_ENTRIES = register(
    "spark.rapids.tpu.server.preparedCache.maxEntries", 64,
    "Statements the prepared-statement plan cache holds (LRU beyond it; "
    "entries are keyed by the spec's structural fingerprint from "
    "cache/keys.statement_fingerprint and SHARED across connections, so "
    "a fleet of clients preparing the same template hits one entry).",
    check=lambda v: None if v >= 1 else "must be >= 1")

SERVER_SPOOL_DIR = register(
    "spark.rapids.tpu.server.spool.dir", "",
    "Directory for disk-backed result spooling (server/spool.py). A "
    "result stream beyond spool.memoryBytes (a large collect, or a "
    "client reading slower than the device produces) overflows to a "
    "crc-framed spool file here instead of growing host memory; the "
    "producer never blocks on the client, so the semaphore permit is "
    "released as soon as the query finishes computing. Empty = "
    "<memory.spill.dir>/server_spool.")

SERVER_SPOOL_MEMORY_BYTES = register(
    "spark.rapids.tpu.server.spool.memoryBytes", 32 << 20,
    "In-memory buffer per result stream before frames overflow to the "
    "disk spool.", conv=int,
    check=lambda v: None if v >= 0 else "must be >= 0")

SERVER_DRAIN_DEADLINE_MS = register(
    "spark.rapids.tpu.server.drain.deadlineMs", 30000.0,
    "Graceful-drain deadline (ms) for planned maintenance: how long "
    "SqlFrontDoor.drain()/QueryScheduler.drain() let in-flight queries "
    "finish after admission stops before cancelling the stragglers "
    "AS-RESUBMITTABLE (typed QueryFaulted(resubmittable) the caller "
    "re-routes to a sibling). Admission stops immediately either way; "
    "the deadline only bounds how long running work may ride out the "
    "restart.", conv=float,
    check=lambda v: None if v >= 0 else "must be >= 0")

TELEMETRY_ENABLED = register(
    "spark.rapids.tpu.telemetry.enabled", True,
    "Master switch for the live metrics registry (utils/telemetry.py): "
    "labeled counters/gauges/log-bucket histograms fed from the "
    "engine's instrumentation choke points (QueryStats fold-in, "
    "scheduler/admission/breaker/brownout transitions, front-door "
    "stream/spool/shed paths, DCN membership events), scraped through "
    "the ops endpoint (/metrics Prometheus exposition, /snapshot "
    "JSON) and shipped as compact deltas on DCN heartbeats for the "
    "coordinator's fleet rollup. Disabled, every emit point is a "
    "single attribute read (the measured overhead bound is the "
    "telemetry_overhead bench line).")

SERVER_OPS_ENABLED = register(
    "spark.rapids.tpu.server.ops.enabled", True,
    "Start the plaintext HTTP ops listener beside each front door "
    "(server/ops.py): GET /metrics (Prometheus exposition), /healthz "
    "(drain/brownout/quarantine-aware liveness), and /snapshot (the "
    "unified scheduler/admission/breaker/quota/cache/telemetry/SLO "
    "JSON the srtop console and loadgen's reconciliation read). The "
    "same payloads are also served over the wire protocol's typed OPS "
    "op, so a fleet scraper may use either surface.")

SERVER_OPS_PORT = register(
    "spark.rapids.tpu.server.ops.port", 0,
    "TCP port for the HTTP ops listener (0 picks an ephemeral port; "
    "SqlFrontDoor.ops_port reports it). Binds server.host.",
    check=lambda v: None if 0 <= v < 65536 else "must be in [0, 65536)")

SERVER_SLO_LATENCY_MS = register(
    "spark.rapids.tpu.server.slo.latencyMs", 2000.0,
    "Per-tenant latency objective: a completed query slower than this "
    "(or one that failed) is an SLO-bad event in the burn-rate "
    "tracker. Feeds the slo_good_total/slo_bad_total counters and the "
    "multi-window slo_burn_rate gauges tools/srtop.py renders.",
    conv=float, check=lambda v: None if v > 0 else "must be > 0")

SERVER_SLO_TARGET = register(
    "spark.rapids.tpu.server.slo.target", 0.99,
    "SLO success-ratio objective (e.g. 0.99 = 1% error budget): the "
    "burn rate is observed_error_rate / (1 - target), so 1.0 means "
    "the budget burns exactly at its sustainable rate and >1 "
    "exhausts it early.", conv=float,
    check=lambda v: None if 0.0 < v < 1.0 else "must be in (0, 1)")

SERVER_SLO_WINDOWS = register(
    "spark.rapids.tpu.server.slo.windows", "60,600",
    "Comma list of trailing window lengths in SECONDS over which the "
    "burn-rate gauges are computed (the classic multi-window "
    "fast-burn/slow-burn alerting pair). Each window exports one "
    "slo_burn_rate{tenant,window} gauge.")

SERVER_MAX_FRAME_BYTES = register(
    "spark.rapids.tpu.server.maxFrameBytes", 256 << 20,
    "Largest BATCH (Arrow IPC result) frame the wire protocol will "
    "accept, enforced against the length prefix BEFORE any payload "
    "allocation — a lying 2 GB length header is answered with a typed "
    "BAD_REQUEST and the connection closes without ever allocating. "
    "Result batches are device-batch sized, far below this.", conv=int,
    check=lambda v: None if 1 <= v <= (1 << 31)
    else "must be in [1, 2^31]")

SERVER_MAX_CONTROL_FRAME_BYTES = register(
    "spark.rapids.tpu.server.maxControlFrameBytes", 4 << 20,
    "Largest JSON control frame (HELLO/SUBMIT/PREPARE/EXECUTE/...) the "
    "wire protocol will accept — much smaller than maxFrameBytes, "
    "because control payloads are small canonical JSON and a huge one "
    "is an attack, not a query. Enforced before allocation; the "
    "server's inbound side applies THIS cap to every frame (a client "
    "never legitimately sends batch frames).", conv=int,
    check=lambda v: None if 1 <= v <= (1 << 31)
    else "must be in [1, 2^31]")

SERVER_HANDSHAKE_TIMEOUT_MS = register(
    "spark.rapids.tpu.server.handshakeTimeoutMs", 5000.0,
    "Deadline (ms) for a fresh connection's FIRST complete frame (the "
    "HELLO): a dialer that connects and trickles — or sends nothing — "
    "is reaped with a typed BAD_REQUEST at this deadline instead of "
    "holding a connection slot for idleTimeout. Distinct from (and "
    "much shorter than) idleTimeout, which governs authenticated "
    "connections between requests.", conv=float,
    check=lambda v: None if v > 0 else "must be > 0")

SERVER_FRAME_TIMEOUT_MS = register(
    "spark.rapids.tpu.server.frameTimeoutMs", 10000.0,
    "Per-frame read-progress deadline (ms): once a frame's first byte "
    "arrives, the WHOLE frame (header + payload) must complete within "
    "this window. The slowloris defense — a client trickling one byte "
    "per idleTimeout makes steady per-recv progress but never finishes "
    "a frame; this deadline reaps it typed. 0 disables (the client "
    "side runs without it; its request timeout bounds the exchange).",
    conv=float, check=lambda v: None if v >= 0 else "must be >= 0")

SERVER_MAX_DECODE_ERRORS = register(
    "spark.rapids.tpu.server.maxDecodeErrors", 3,
    "Per-connection strike budget for malformed frames: each decode "
    "failure the stream can resync past (unknown frame type, crc "
    "mismatch) is answered with a typed BAD_REQUEST and counted; a "
    "connection burning the budget is disconnected and its peer "
    "address enters the dial-refusal penalty box "
    "(server.penaltyBoxMs). Non-resyncable failures (an oversized "
    "length prefix, a mid-frame stall) disconnect on the first "
    "strike — the declared payload boundary cannot be trusted.",
    check=lambda v: None if v >= 1 else "must be >= 1")

SERVER_PENALTY_BOX_MS = register(
    "spark.rapids.tpu.server.penaltyBoxMs", 2000.0,
    "Dial-refusal window (ms) for a peer address whose connection "
    "burned its decode-error strike budget: new dials from that "
    "address are answered with a typed REJECTED (reason penalty_box, "
    "retry_after_ms = the remaining window) and closed before a "
    "handler thread is spent on them. Deliberately SHORT — on a "
    "loopback dev fleet every client shares one address, so the box "
    "is a storm brake, not a ban. 0 disables.", conv=float,
    check=lambda v: None if v >= 0 else "must be >= 0")

SERVER_MAX_INFLIGHT_PER_CONN = register(
    "spark.rapids.tpu.server.maxInflightPerConn", 8,
    "Cap on wire queries one connection may hold in the in-flight "
    "registry at once, shed typed REJECTED (reason conn_inflight) "
    "beyond it. The protocol is sequential request->response today, "
    "so a well-formed client never sees this; it bounds the blast "
    "radius of any future pipelining bug or a hostile client racing "
    "the registry.",
    check=lambda v: None if v >= 1 else "must be >= 1")

SERVER_SPEC_MAX_DEPTH = register(
    "spark.rapids.tpu.server.spec.maxDepth", 32,
    "Deepest nesting (expression trees included) a wire query spec may "
    "carry. Validated ITERATIVELY ahead of compile (server/spec.py "
    "validate_spec), so a recursion-bomb spec is answered with a typed "
    "BAD_REQUEST and the planner never recurses past the cap.",
    check=lambda v: None if v >= 1 else "must be >= 1")

SERVER_SPEC_MAX_NODES = register(
    "spark.rapids.tpu.server.spec.maxNodes", 10000,
    "Total JSON nodes (objects, lists, scalars) a wire query spec may "
    "carry — the width-bomb bound paired with spec.maxDepth's depth "
    "bound. Typed BAD_REQUEST beyond it.",
    check=lambda v: None if v >= 1 else "must be >= 1")

SERVER_SPEC_MAX_OPS = register(
    "spark.rapids.tpu.server.spec.maxOps", 64,
    "Longest op pipeline a wire query spec may carry. Typed "
    "BAD_REQUEST beyond it.",
    check=lambda v: None if v >= 1 else "must be >= 1")

SERVER_SPEC_MAX_PARAMS = register(
    "spark.rapids.tpu.server.spec.maxParams", 64,
    "Most parameter slots a wire query spec may declare; param INDICES "
    "are bounded by the same cap (indices must be contiguous from 0), "
    "so a spec declaring ['param', 10^9, ...] is rejected typed "
    "instead of driving a billion-element contiguity check.",
    check=lambda v: None if v >= 1 else "must be >= 1")

SERVER_SPEC_MAX_STRING_BYTES = register(
    "spark.rapids.tpu.server.spec.maxStringBytes", 65536,
    "Total UTF-8 bytes of string values (literals, names, op fields) a "
    "wire query spec may carry. Typed BAD_REQUEST beyond it.",
    check=lambda v: None if v >= 1 else "must be >= 1")

SERVER_SPEC_MAX_JOINS = register(
    "spark.rapids.tpu.server.spec.maxJoins", 8,
    "Most join ops one wire query spec may carry (join fan-in): each "
    "join multiplies planning and execution cost, so the resource-bomb "
    "bound is separate from — and much smaller than — spec.maxOps.",
    check=lambda v: None if v >= 1 else "must be >= 1")

SERVER_OPS_MAX_REQUEST_BYTES = register(
    "spark.rapids.tpu.server.ops.maxRequestBytes", 16384,
    "Byte cap on an ops-listener HTTP request head (request line + "
    "headers): a scrape request larger than this is dropped and the "
    "connection closed (ops_requests_rejected_total{reason=oversize}) "
    "— the ops surface serves tiny GETs, anything bigger is hostile.",
    conv=int, check=lambda v: None if v >= 256 else "must be >= 256")

SERVER_OPS_REQUEST_TIMEOUT_MS = register(
    "spark.rapids.tpu.server.ops.requestTimeoutMs", 10000.0,
    "Wall deadline (ms) for reading one ops-listener HTTP request head "
    "AND the per-recv socket timeout on its connection: a scraper "
    "trickling header bytes is reaped here instead of pinning an ops "
    "handler thread (ops_requests_rejected_total{reason=slow}).",
    conv=float, check=lambda v: None if v > 0 else "must be > 0")

SERVER_DRAIN_SIBLINGS = register(
    "spark.rapids.tpu.server.drain.siblings", "",
    "Comma list of 'host:port' sibling front doors advertised in the "
    "GOAWAY control frame during a drain, so a WireClient reconnects "
    "and retries idempotently against a live endpoint instead of "
    "failing. Empty = the GOAWAY names no siblings (clients retry "
    "their own endpoint after the restart). SqlFrontDoor.drain() may "
    "also be passed an explicit sibling list (the rolling-restart "
    "driver's mode, where the surviving fleet is known).")


class TpuConf:
    """An immutable snapshot of settings; unset keys resolve to defaults."""

    _session_lock = threading.Lock()
    _session_overrides: Dict[str, Any] = {}

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        merged = dict(TpuConf._session_overrides)
        merged.update(settings or {})
        self._values: Dict[str, Any] = {}
        for k, v in merged.items():
            entry = ALL_ENTRIES.get(k)
            if entry is None:
                raise KeyError(f"unknown config key {k!r}; see TpuConf.help()")
            self._values[k] = entry.convert(v)

    def get(self, entry: ConfEntry) -> Any:
        return self._values.get(entry.key, entry.default)

    def is_set(self, key: str) -> bool:
        """True when the key was explicitly set (session override or
        per-query settings) rather than resolving to its default —
        lets backend-aware defaults yield to an operator's explicit
        choice (runtime/pipeline.effective_depth)."""
        return key in self._values

    def __getitem__(self, key: str) -> Any:
        entry = ALL_ENTRIES[key]
        return self._values.get(key, entry.default)

    def with_settings(self, **kv) -> "TpuConf":
        vals = dict(self._values)
        vals.update(kv)
        return TpuConf(vals)

    # -- session-level mutation (Session.conf.set style) --------------------------
    @classmethod
    def set_session(cls, key: str, value: Any) -> None:
        entry = ALL_ENTRIES.get(key)
        if entry is None:
            raise KeyError(f"unknown config key {key!r}")
        with cls._session_lock:
            cls._session_overrides[key] = entry.convert(value)

    @classmethod
    def unset_session(cls, key: str) -> None:
        with cls._session_lock:
            cls._session_overrides.pop(key, None)

    @classmethod
    def clear_session(cls) -> None:
        with cls._session_lock:
            cls._session_overrides.clear()

    # -- documentation generation -------------------------------------------------
    @staticmethod
    def help(include_internal: bool = False) -> str:
        """Markdown table of every registered key (docs generator analog)."""
        lines = ["| Key | Default | Description |", "|---|---|---|"]
        for key in sorted(ALL_ENTRIES):
            e = ALL_ENTRIES[key]
            if e.internal and not include_internal:
                continue
            lines.append(f"| {e.key} | {e.default} | {e.doc} |")
        return "\n".join(lines)


XLA_CACHE_DIR = register(
    "spark.rapids.tpu.xla.cacheDir", ".cache/xla",
    "Persistent XLA compilation cache directory; compiled programs survive "
    "process restarts. A relative path is resolved against the checkout "
    "(the directory that holds the spark_rapids_tpu package), so every "
    "process of one checkout shares one cache. When the environment sets "
    "JAX_COMPILATION_CACHE_DIR that directory is used instead and this "
    "key is ignored. Empty disables.", startup_only=True)

# -- warm-start subsystem (runtime/warmstore.py, plan/bucketing.py) -----------

WARMSTORE_ENABLED = register(
    "spark.rapids.tpu.warmstore.enabled", True,
    "Warm-start subsystem: persist a content-addressed index of compiled "
    "statements (fingerprint x bucket x topology) over the XLA compilation "
    "cache, ship hot entries to drain siblings, and prewarm them after "
    "restart (docs/warmstart.md).")

WARMSTORE_DIR = register(
    "spark.rapids.tpu.warmstore.dir", "warmstore",
    "Directory for the warm-start store's index manifest. A relative path "
    "is resolved against the XLA compilation cache directory in effect "
    "(JAX_COMPILATION_CACHE_DIR, else xla.cacheDir): the index describes "
    "the executables cached there and travels with them. Unwritable paths "
    "degrade to an in-memory store (warmstore_errors_total{kind=store_dir}) "
    "instead of failing startup. Empty keeps the store in-memory only.",
    startup_only=True)

WARMSTORE_MAX_ENTRIES = register(
    "spark.rapids.tpu.warmstore.maxEntries", 256,
    "LRU bound on warm-start index entries; the coldest entry is evicted "
    "past this.", conv=int,
    check=lambda v: None if v >= 1 else "must be >= 1")

WARMSTORE_MAX_BYTES = register(
    "spark.rapids.tpu.warmstore.maxBytes", 4 * 1024 * 1024,
    "LRU bound on the serialized warm-start index size (bytes); evicts "
    "coldest-first until under.", conv=int,
    check=lambda v: None if v >= 4096 else "must be >= 4096")

WARMSTORE_SHIP_TOP_N = register(
    "spark.rapids.tpu.warmstore.ship.topN", 32,
    "How many of the hottest warm-start entries a draining door ships to "
    "each GOAWAY sibling before exit. 0 disables shipping.", conv=int,
    check=lambda v: None if v >= 0 else "must be >= 0")

WARMSTORE_PREWARM_ENABLED = register(
    "spark.rapids.tpu.warmstore.prewarm.enabled", True,
    "Background-compile the store's hottest statement fingerprints at door "
    "startup (and on shipped imports), prioritized by the admission cost "
    "model's traffic profiles.")

WARMSTORE_PREWARM_MAX_STATEMENTS = register(
    "spark.rapids.tpu.warmstore.prewarm.maxStatements", 16,
    "Upper bound on statements one prewarm pass compiles.", conv=int,
    check=lambda v: None if v >= 0 else "must be >= 0")

WARMSTORE_PREWARM_BUDGET_S = register(
    "spark.rapids.tpu.warmstore.prewarm.budgetS", 30.0,
    "Wall-clock budget (seconds) for one prewarm pass; the pass stops at "
    "the first entry boundary past it so prewarm can never monopolize the "
    "device semaphore.", conv=float,
    check=lambda v: None if v >= 0 else "must be >= 0")

WARMSTORE_BUCKET_GROWTH = register(
    "spark.rapids.tpu.warmstore.bucket.growth", 2.0,
    "Geometric step between capacity-bucket rungs. 2.0 is the classic "
    "power-of-two ladder; smaller steps (>= 1.05, e.g. 1.25) trade more "
    "compiled programs for less padding waste per batch.", conv=float,
    check=lambda v: None if v >= 1.05 else "must be >= 1.05")

WARMSTORE_BUCKET_ALIGN = register(
    "spark.rapids.tpu.warmstore.bucket.align", 1,
    "Round every bucket rung up to a multiple of this (set 128, the TPU "
    "lane width, with non-power-of-two growth so padded shapes stay "
    "lane-aligned).", conv=int,
    check=lambda v: None if v >= 1 else "must be >= 1")

WARMSTORE_BUCKET_MIN_ROWS_STRING = register(
    "spark.rapids.tpu.warmstore.bucket.minRowsString", 0,
    "Per-dtype bucket minimum: batches carrying host string columns get at "
    "least this capacity (string uploads amortize worse). 0 disables.",
    conv=int, check=lambda v: None if v >= 0 else "must be >= 0")

CBO_ENABLED = register(
    "spark.rapids.tpu.sql.cbo.enabled", False,
    "Cost-based optimizer: revert device placement for plan sections whose "
    "estimated row volume is too small to be worth device dispatch "
    "(CostBasedOptimizer.scala analog; off by default like the reference).")

CBO_MIN_DEVICE_ROWS = register(
    "spark.rapids.tpu.sql.cbo.minDeviceRows", 1024,
    "With CBO enabled: minimum estimated rows for a plan section to stay "
    "on the device.")


AGG_GRID_MAX_GROUPS = register(
    "spark.rapids.tpu.sql.agg.gridMaxGroups", 4096,
    "Grouped aggregation uses a dense-grid reduction (no sort, no "
    "permutation gathers) when every group key is a dictionary-coded "
    "string and the padded grid has at most this many slots.")

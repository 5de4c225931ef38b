"""Process-global live metrics registry: the fleet telemetry plane.

Everything before this module answered "where did the time go" for ONE
query after the fact (utils/tracing.py spans, ``trace_report.py``) or
for one process if you could call ``snapshot()`` in-process.  A fleet
of front doors over a distributed engine needs the complement: LIVE,
named, labeled counters/gauges/histograms any scraper can read while
the service runs — the signal Theseus-style placement and the
admission cost loop consume continuously instead of per-trace.

Design rules (the ``protocol.ERROR_CODES`` discipline, applied to
metric names):

  * **one canonical vocabulary** — every metric is declared ONCE in
    :data:`METRICS` (name, kind, labels, help).  srtlint's
    ``metrics-registry`` pass holds every ``telemetry.count`` /
    ``gauge_set`` / ``observe`` call site to it, two ways: an
    unregistered name at a call site and a registered name nobody
    emits are both findings.  The docs catalog in
    ``docs/observability.md`` is generated from the same table
    (:func:`catalog_md`), so it cannot drift;
  * **near-zero when off** — every entry point is one attribute read
    plus a return when ``spark.rapids.tpu.telemetry.enabled`` is
    false;
  * **lock-cheap when on** — one process lock, held only for a dict
    update (no I/O, no allocation beyond the series entry).  Scrapes
    copy under the lock and render outside it, so a scrape storm never
    blocks the query path;
  * **fleet-mergeable** — counters and histogram buckets are
    monotonic sums, shipped as compact cumulative deltas on DCN
    heartbeats (:func:`wire_delta`) and merged per-rank at the
    coordinator (replacement per series, summation across ranks), so
    duplicate delivery and coordinator failover (the journal carries
    the per-rank views) cannot double-count.  Gauges stay rank-local.

The SLO layer rides the same registry: per-tenant good/bad events
(latency under ``server.slo.latencyMs`` AND a clean status) feed
multi-window burn-rate gauges (``slo_burn_rate{tenant,window}``)
recomputed at scrape time — ``tools/srtop.py`` renders them live.
"""

from __future__ import annotations

import bisect
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

__all__ = ["METRICS", "count", "gauge_set", "observe", "configure",
           "enabled", "snapshot", "render_prometheus", "catalog_md",
           "wire_delta", "merge_rank", "fleet", "set_fleet",
           "fold_query_stats", "slo_observe", "slo_snapshot",
           "slo_latency_s",
           "register_provider", "reset_for_tests", "HIST_BOUNDS"]

# ---------------------------------------------------------------------------------
# THE canonical metric vocabulary.  (name, kind, labels, help) — kept a
# pure literal so srtlint's metrics-registry pass (and catalog_md) can
# read it without executing anything.  kind: counter | gauge |
# histogram.  labels: space-separated label names ("" = unlabeled).
# ---------------------------------------------------------------------------------

METRICS = (
    # -- scheduler / admission / containment ---------------------------------------
    ("queries_submitted_total", "counter", "tenant",
     "Queries admitted into the scheduler queue, by tenant."),
    ("queries_completed_total", "counter", "status tenant",
     "Scheduler queries reaching a terminal status (done/failed/"
     "faulted/cancelled/deadline/drained), by status and tenant."),
    ("queries_shed_total", "counter", "reason",
     "Typed admission sheds by reason (queue_full/doomed/overload/"
     "draining/closed/quarantined/brownout/quota) — the overload "
     "taxonomy on the wire, as a live counter."),
    ("query_latency_seconds", "histogram", "tenant",
     "Submit-to-finish service latency (queue wait included) of "
     "completed scheduler queries, log-bucketed, by tenant."),
    ("queue_depth", "gauge", "",
     "Queries waiting in the scheduler admission queue right now."),
    ("queries_running", "gauge", "",
     "Queries in flight on scheduler workers right now."),
    ("brownout_active", "gauge", "",
     "1 while the scheduler serves in brownout (alive capacity below "
     "scheduler.brownout.enterFraction), else 0."),
    ("breaker_transitions_total", "counter", "state",
     "Circuit-breaker transitions by destination state "
     "(open/half_open/closed/reopened)."),
    ("breakers_open", "gauge", "",
     "Statement fingerprints currently quarantined (breaker open or "
     "half-open)."),
    # -- network front door --------------------------------------------------------
    ("server_connections_total", "counter", "",
     "TCP connections accepted by the front door (rejected ones "
     "included)."),
    ("server_connections_rejected_total", "counter", "",
     "Connections shed at the maxConnections cap."),
    ("server_queries_total", "counter", "",
     "Wire queries submitted into the scheduler by the front door."),
    ("server_queries_streamed_total", "counter", "",
     "Wire queries whose result stream finished with an END frame."),
    ("server_stream_bytes_total", "counter", "",
     "Bytes of BATCH frames (header included) written to result "
     "streams."),
    ("server_spool_bytes_total", "counter", "",
     "Result-stream bytes that overflowed to the disk spool."),
    ("server_goaways_total", "counter", "",
     "GOAWAY frames sent while draining."),
    ("server_conn_lost_total", "counter", "",
     "Connections that dropped with a query mid-stream."),
    ("server_wire_errors_total", "counter", "code",
     "ERROR frames sent, by protocol.ERROR_CODES code — reconciles "
     "exactly with client-observed typed errors."),
    ("ops_scrapes_total", "counter", "endpoint",
     "Ops-surface reads served (/metrics, /healthz, /snapshot, "
     "/debug/slow, and the OPS wire op)."),
    ("server_decode_errors_total", "counter", "kind",
     "Frames that failed to decode at the front door, by failure kind "
     "(oversize/unknown_type/crc/unexpected/slow/handshake/injected) — "
     "each costs the connection a strike against "
     "server.maxDecodeErrors."),
    ("server_hostile_disconnects_total", "counter", "reason",
     "Connections the front door disconnected for hostile input, by "
     "reason (strikes = budget burned, oversize = untrusted frame "
     "boundary, slow = frame deadline, handshake = no HELLO in time)."),
    ("server_penalty_refusals_total", "counter", "",
     "Dials refused at accept because the peer address was in the "
     "strike-budget penalty box (typed REJECTED, reason penalty_box)."),
    ("ops_requests_rejected_total", "counter", "reason",
     "Ops-listener HTTP requests dropped at the read guard, by reason "
     "(oversize = request head over ops.maxRequestBytes, slow = head "
     "not complete within ops.requestTimeoutMs)."),
    # -- DCN / fleet ---------------------------------------------------------------
    ("dcn_epoch", "gauge", "",
     "This rank's view of the cluster membership epoch."),
    ("dcn_alive_ranks", "gauge", "",
     "Alive ranks in the last membership event this process saw."),
    # -- SLO burn ------------------------------------------------------------------
    ("slo_good_total", "counter", "tenant",
     "Completed queries inside the tenant's latency SLO."),
    ("slo_bad_total", "counter", "tenant",
     "Completed queries violating the tenant's latency SLO (late or "
     "failed)."),
    ("slo_burn_rate", "gauge", "tenant window",
     "Error-budget burn rate per tenant per trailing window (1.0 = "
     "burning exactly the budget; >1 exhausts it early).  Recomputed "
     "at scrape time from the rolling event log."),
    # -- observability self-accounting ---------------------------------------------
    ("trace_events_dropped_total", "counter", "",
     "Trace events dropped past sql.trace.maxEvents — a truncated "
     "trace is visibly truncated."),
    # -- per-query accounting folded from QueryStats at scope exit -----------------
    ("query_blocking_fetches_total", "counter", "",
     "Blocking device-to-host fetches across all finished queries."),
    ("query_async_fetches_total", "counter", "",
     "Async (pipelined) device-to-host fetches across all finished "
     "queries."),
    ("query_fetch_bytes_total", "counter", "",
     "Device-to-host bytes moved by finished queries."),
    ("query_fetch_wait_seconds_total", "counter", "",
     "Wall seconds spent blocked inside device_get."),
    ("query_compiles_total", "counter", "",
     "XLA program compiles observed."),
    ("query_compile_seconds_total", "counter", "",
     "Wall seconds spent in XLA compilation."),
    ("query_uploads_total", "counter", "",
     "Host-to-device uploads issued by finished queries."),
    ("query_upload_bytes_total", "counter", "",
     "Host-to-device bytes uploaded by finished queries."),
    ("query_upload_seconds_total", "counter", "",
     "Seconds inside jax.device_put (scan:upload spans), summed over "
     "the threads that upload."),
    ("query_decode_seconds_total", "counter", "",
     "Seconds inside scan:decode spans, summed over the threads that "
     "decode."),
    # mesh fragments (parallel/spmd.py, shuffle.mode=ICI)
    ("query_ici_fragments_total", "counter", "",
     "Plan fragments run on the ICI mesh by finished queries."),
    ("query_ici_materialize_seconds_total", "counter", "",
     "Seconds inside ici:materialize spans: a fragment's leaves run "
     "single-process and brought to the host."),
    ("query_ici_feed_seconds_total", "counter", "",
     "Seconds inside ici:feed spans: leaves padded and placed on the "
     "mesh."),
    ("query_ici_step_seconds_total", "counter", "",
     "Seconds inside ici:step spans: a fragment's mesh programs and the "
     "fetches of their row counts."),
    ("query_ici_gather_seconds_total", "counter", "",
     "Seconds inside ici:gather spans: a fragment's outputs to a host "
     "table."),
    ("query_ici_feed_bytes_total", "counter", "",
     "Bytes placed on the mesh as padded (every device's copy of a "
     "replicated leaf)."),
    ("query_ici_exchange_bytes_total", "counter", "",
     "Bytes the ICI all_to_alls move, from the static bucket shapes "
     "(n_dev * n_dev * bucket rows * row width an exchange)."),
    ("query_ici_overflow_retries_total", "counter", "",
     "Fragments re-run at 4x capacities after a bucket or join "
     "expansion overflowed."),
    ("query_ici_compacted_exchanges_total", "counter", "",
     "ICI exchanges that bucketed fewer rows than their input's capacity "
     "(cut to the ladder rung over the fullest sender's counted rows)."),
    ("query_agg_dense_batches_total", "counter", "",
     "Batches the dense aggregation's update programs took."),
    ("query_agg_dense_compacted_batches_total", "counter", "",
     "Dense-aggregation batches whose in-domain rows were compacted on "
     "the device before the scatters (chosen in the program from the "
     "row count)."),
    ("query_agg_merges_total", "counter", "",
     "Runs of the agg_merge_grouped program: sort-path partial results "
     "concatenated and reduced again."),
    ("query_agg_merge_parts_total", "counter", "",
     "Partial results that entered those merges: over "
     "query_agg_merges_total, 2.0 is one merge a part, 9.0 a nine-set "
     "rollup merged once."),
    ("query_distinct_one_pass_aggs_total", "counter", "",
     "count(DISTINCT) aggregates planned as two stacked aggregates over "
     "one copy of their child (none where the join form ran the child "
     "once more a distinct set)."),
    ("query_page_coded_keys_total", "counter", "",
     "String group keys an aggregate coded from the parquet pages' "
     "dictionary codes, one a key column a batch: the page dictionaries "
     "remapped, no row hashed."),
    # the reporting operators (window, expand) and CPU placement
    ("query_window_exec_seconds_total", "counter", "",
     "Seconds inside window:exec spans: a window's input concatenated "
     "and compacted, its program, the gather into sorted order."),
    ("query_window_rows_total", "counter", "",
     "Live rows that entered device windows."),
    ("query_expand_exec_seconds_total", "counter", "",
     "Seconds inside expand:project spans: one grouping set's "
     "projection of one batch."),
    ("query_expand_slot_rows_total", "counter", "",
     "Slots grouping-set expansions handed on (capacity x projections "
     "x batches, live or not)."),
    ("query_cpu_fallback_nodes_total", "counter", "",
     "Plan nodes placed on the CPU (CpuOpExec) in plans that were run."),
    # the joins (plan/join_exec.py)
    ("query_join_exec_seconds_total", "counter", "",
     "Seconds inside join:pair spans: one probe batch joined with its "
     "build side (the children's work is outside it)."),
    ("query_join_pairs_total", "counter", "",
     "Candidate pairs join expansions were sized for (every key match, "
     "before a condition drops any)."),
    ("query_join_out_slots_total", "counter", "",
     "Output slots join expansions ran at (the capacity rung over their "
     "candidate pairs)."),
    ("query_join_semi_anti_total", "counter", "",
     "Semi, anti and existence joins run, a probe batch each."),
    # the host-time account (utils/tracing.account): disjoint shares of
    # the driving thread's time; the nine terms sum to the wall
    ("query_wall_seconds_total", "counter", "",
     "Wall seconds of finished queries, entry point to rows."),
    ("query_acct_plan_seconds_total", "counter", "",
     "Account: planning (plan:* spans' self time)."),
    ("query_acct_admit_seconds_total", "counter", "",
     "Account: waiting for a device permit (admit:semaphore)."),
    ("query_acct_compile_seconds_total", "counter", "",
     "Account: backend compiles on the driving thread."),
    ("query_acct_h2d_wait_seconds_total", "counter", "",
     "Account: the driving thread blocked on a staged batch "
     "(pipeline:wait, scan:wait)."),
    ("query_acct_fetch_wait_seconds_total", "counter", "",
     "Account: the driving thread blocked inside device_get."),
    ("query_acct_dispatch_seconds_total", "counter", "",
     "Account: the driving thread inside the JAX runtime and not "
     "fetching (program:*, eager:gather, scan:upload, "
     "fetch:start_copies)."),
    ("query_acct_result_seconds_total", "counter", "",
     "Account: result materialisation (result:* spans)."),
    ("query_acct_host_exec_seconds_total", "counter", "",
     "Account: the program's own Python inside operators (op:* self "
     "time)."),
    ("query_acct_unattributed_seconds_total", "counter", "",
     "Account: wall under no span at all."),
    # the driving thread's h2d wait resolved through the producer threads
    # it waited on (tracing.RESOLVED_TERMS); the seven sum to the wait
    ("query_acct_h2d_decode_seconds_total", "counter", "",
     "Account, h2d wait resolved: a producer in scan:decode."),
    ("query_acct_h2d_convert_seconds_total", "counter", "",
     "Account, h2d wait resolved: a producer in op:scanTime's self time "
     "(Arrow to numpy, padding to the bucket)."),
    ("query_acct_h2d_upload_seconds_total", "counter", "",
     "Account, h2d wait resolved: a producer in scan:upload."),
    ("query_acct_h2d_dispatch_seconds_total", "counter", "",
     "Account, h2d wait resolved: a producer in its other dispatch "
     "spans (program:*, eager:gather, fetch:start_copies)."),
    ("query_acct_h2d_fetch_wait_seconds_total", "counter", "",
     "Account, h2d wait resolved: a producer blocked on the device "
     "(fetch:blocking, fetch:async)."),
    ("query_acct_h2d_host_exec_seconds_total", "counter", "",
     "Account, h2d wait resolved: a producer's other spanned time (op:* "
     "self time, join:pair, window:exec, pipeline:stage, compiles)."),
    ("query_acct_h2d_handoff_seconds_total", "counter", "",
     "Account, h2d wait resolved: what no producer span covered (queue "
     "hand-off, wake-up, the GIL, a producer under no span)."),
    ("query_shuffle_bytes_total", "counter", "",
     "Bytes entering shuffle exchanges."),
    ("query_h2d_wait_seconds_total", "counter", "",
     "Consumer wall seconds blocked waiting on pipeline-staged "
     "batches."),
    ("query_fused_regions_total", "counter", "",
     "Fused plan regions executed (plan/fusion.py region planner)."),
    ("query_region_fetches_total", "counter", "",
     "Blocking fetches paid through fused regions' batched prologues "
     "(a subset of query_blocking_fetches_total)."),
    ("query_spill_events_total", "counter", "",
     "Device-to-host spill demotions charged to query scopes."),
    ("cache_hits_total", "counter", "",
     "Cross-query device cache hits (scan + broadcast tiers)."),
    ("cache_misses_total", "counter", "",
     "Cross-query device cache misses."),
    ("cache_hit_bytes_total", "counter", "",
     "Bytes served from the cross-query cache instead of "
     "decode+upload."),
    ("cache_evictions_total", "counter", "",
     "Cross-query cache entries dropped (budget/TTL/invalidation)."),
    ("cache_evict_bytes_total", "counter", "",
     "Bytes dropped with evicted cross-query cache entries."),
    ("faults_injected_total", "counter", "",
     "Faults the seeded injector fired."),
    ("transient_retries_total", "counter", "",
     "Retries the transient-recovery layer issued."),
    ("retry_backoff_seconds_total", "counter", "",
     "Wall seconds spent in transient-retry backoff."),
    ("fragments_recomputed_total", "counter", "",
     "Shuffle fragments re-pulled from durable map output after a "
     "fault."),
    ("fragments_recomputed_remote_total", "counter", "",
     "Fragments re-pulled from a DEAD peer's durable map output."),
    ("fragments_hedged_total", "counter", "",
     "Slow-peer fragment fetches raced against durable map output."),
    ("degraded_batches_total", "counter", "",
     "Batches that ran the cpu/ degradation path after device "
     "retries exhausted."),
    ("dcn_peers_lost_total", "counter", "",
     "Peers declared dead while queries ran."),
    ("dcn_partitions_reowned_total", "counter", "",
     "Reduce partitions re-owned across a shrunk group."),
    ("queries_resubmitted_total", "counter", "",
     "Whole-query scheduler resubmissions after "
     "permanent-at-this-placement failures."),
    ("dcn_frames_deduped_total", "counter", "",
     "Duplicated/reordered DCN frames answered from the dedup "
     "journal."),
    ("dcn_quorum_losses_total", "counter", "",
     "Times a rank parked typed on the minority side of a "
     "partition."),
    ("dcn_rank_rejoins_total", "counter", "",
     "Parked ranks that healed and re-registered."),
    ("dcn_coordinator_failovers_total", "counter", "",
     "Coordinator failovers this process performed or followed."),
    ("integrity_failures_total", "counter", "",
     "Checksum verifications that failed (silent corruption caught "
     "and routed into recovery)."),
    ("watchdog_stalls_total", "counter", "",
     "Queries the watchdog declared stalled."),
    ("prepared_hits_total", "counter", "",
     "Prepared-statement plan-cache hits."),
    ("prepared_misses_total", "counter", "",
     "Prepared-statement plan-cache misses."),
    # -- performance flight recorder (utils/recorder.py) ---------------------------
    ("recorder_captures_total", "counter", "reason",
     "Query traces the flight recorder retained, by retention reason "
     "(slo / outcome / first_seen / top_k)."),
    ("recorder_dropped_total", "counter", "reason",
     "Query traces the flight recorder let go: the boring median "
     "(reason=boring) and ring evictions past maxQueries/maxBytes "
     "(reason=evicted)."),
    ("recorder_missed_total", "counter", "",
     "SLO-violating queries that resolved with NO trace to retain — "
     "should stay 0; tools/loadgen.py audits it against "
     "slo_bad_total."),
    ("recorder_queries", "gauge", "",
     "Traces currently held in the flight-recorder ring."),
    ("recorder_bytes", "gauge", "",
     "Approximate bytes held by the flight-recorder ring (the "
     "recorder.maxBytes bound is on this estimate)."),
    ("compiles_by_trigger_total", "counter", "trigger",
     "Backend compiles classified by the compile ledger's trigger "
     "taxonomy (first_seen / shape_change / post_restart / "
     "cache_evict / store_hit for warm-store-served deserializations "
     "/ prewarm for the background warm-up lane, plus unattributed "
     "for session-direct compiles with no statement fingerprint)."),
    ("compile_storm_active", "gauge", "",
     "1 while the recompile-storm detector is tripped (recompiles in "
     "the trailing window above the storm threshold), else 0."),
    ("perf_anomalies_total", "counter", "term",
     "Root-cause verdicts issued at capture seal, by dominant "
     "anomalous wait term (queue_wait / compile / h2d / dispatch / "
     "fetch_wait / shuffle / spill / stream_spool)."),
    ("warmstore_hits_total", "counter", "",
     "Statements that arrived already covered by a warm-start store "
     "entry (a persisted or shipped program served instead of a cold "
     "compile)."),
    ("warmstore_misses_total", "counter", "",
     "Statements the warm-start store had no entry for (the cold "
     "path; seeds a new entry)."),
    ("warmstore_evictions_total", "counter", "",
     "Warm-start store entries evicted by the LRU bounds "
     "(warmstore.maxEntries / warmstore.maxBytes)."),
    ("warmstore_shipped_total", "counter", "direction",
     "Warm-start entries shipped between doors at drain time "
     "(direction=sent by the draining door, direction=received by "
     "its GOAWAY sibling)."),
    ("warmstore_prewarmed_total", "counter", "",
     "Statements the background prewarm lane compiled ahead of "
     "traffic (trigger=prewarm in the compile ledger)."),
    ("warmstore_corrupt_total", "counter", "",
     "Warm-start store loads that hit a corrupt/unreadable manifest "
     "or entry and were dropped (the store degrades, never fails the "
     "door)."),
    ("warmstore_errors_total", "counter", "kind",
     "Warm-start subsystem degradations: kind=cache_dir (XLA "
     "compilation cache dir unwritable — proceeding cold), "
     "kind=store_dir (store dir unwritable — in-memory only), "
     "kind=ship (sibling shipping failed), kind=prewarm (a prewarm "
     "compile failed)."),
    ("warmstore_entries", "gauge", "",
     "Entries currently in the warm-start store index."),
    ("warmstore_bytes", "gauge", "",
     "Approximate serialized size of the warm-start store index "
     "(the warmstore.maxBytes bound is on this estimate)."),
)

# QueryStats field -> registered counter: the ONE fold-in choke point.
# Every query scope that exits to the process aggregate mirrors these
# fields into the registry (fold_query_stats), so the per-query
# accounting PRs 1-14 built becomes a live, scrapeable counter set
# without a second instrumentation pass over the engine.  Names on the
# right are "used" for the metrics-registry two-way check.
_QS_FOLD = (
    ("blocking_fetches", "query_blocking_fetches_total"),
    ("async_fetches", "query_async_fetches_total"),
    ("fetch_bytes", "query_fetch_bytes_total"),
    ("fetch_wait_s", "query_fetch_wait_seconds_total"),
    ("compiles", "query_compiles_total"),
    ("compile_s", "query_compile_seconds_total"),
    ("uploads", "query_uploads_total"),
    ("upload_bytes", "query_upload_bytes_total"),
    ("upload_s", "query_upload_seconds_total"),
    ("decode_s", "query_decode_seconds_total"),
    ("ici_fragments", "query_ici_fragments_total"),
    ("ici_materialize_s", "query_ici_materialize_seconds_total"),
    ("ici_feed_s", "query_ici_feed_seconds_total"),
    ("ici_step_s", "query_ici_step_seconds_total"),
    ("ici_gather_s", "query_ici_gather_seconds_total"),
    ("ici_feed_bytes", "query_ici_feed_bytes_total"),
    ("ici_exchange_bytes", "query_ici_exchange_bytes_total"),
    ("ici_overflow_retries", "query_ici_overflow_retries_total"),
    ("ici_compacted_exchanges", "query_ici_compacted_exchanges_total"),
    ("window_exec_s", "query_window_exec_seconds_total"),
    ("window_rows", "query_window_rows_total"),
    ("expand_exec_s", "query_expand_exec_seconds_total"),
    ("expand_slot_rows", "query_expand_slot_rows_total"),
    ("cpu_fallback_nodes", "query_cpu_fallback_nodes_total"),
    ("join_exec_s", "query_join_exec_seconds_total"),
    ("join_pairs", "query_join_pairs_total"),
    ("join_out_slots", "query_join_out_slots_total"),
    ("join_semi_anti", "query_join_semi_anti_total"),
    ("agg_dense_batches", "query_agg_dense_batches_total"),
    ("agg_dense_compacted_batches",
     "query_agg_dense_compacted_batches_total"),
    ("agg_merges", "query_agg_merges_total"),
    ("agg_merge_parts", "query_agg_merge_parts_total"),
    ("distinct_one_pass_aggs", "query_distinct_one_pass_aggs_total"),
    ("page_coded_keys", "query_page_coded_keys_total"),
    ("query_wall_s", "query_wall_seconds_total"),
    ("acct_plan_s", "query_acct_plan_seconds_total"),
    ("acct_admit_s", "query_acct_admit_seconds_total"),
    ("acct_compile_s", "query_acct_compile_seconds_total"),
    ("acct_h2d_wait_s", "query_acct_h2d_wait_seconds_total"),
    ("acct_fetch_wait_s", "query_acct_fetch_wait_seconds_total"),
    ("acct_dispatch_s", "query_acct_dispatch_seconds_total"),
    ("acct_result_s", "query_acct_result_seconds_total"),
    ("acct_host_exec_s", "query_acct_host_exec_seconds_total"),
    ("acct_unattributed_s", "query_acct_unattributed_seconds_total"),
    ("acct_h2d_decode_s", "query_acct_h2d_decode_seconds_total"),
    ("acct_h2d_convert_s", "query_acct_h2d_convert_seconds_total"),
    ("acct_h2d_upload_s", "query_acct_h2d_upload_seconds_total"),
    ("acct_h2d_dispatch_s", "query_acct_h2d_dispatch_seconds_total"),
    ("acct_h2d_fetch_wait_s", "query_acct_h2d_fetch_wait_seconds_total"),
    ("acct_h2d_host_exec_s", "query_acct_h2d_host_exec_seconds_total"),
    ("acct_h2d_handoff_s", "query_acct_h2d_handoff_seconds_total"),
    ("shuffle_bytes", "query_shuffle_bytes_total"),
    ("h2d_wait_s", "query_h2d_wait_seconds_total"),
    ("fused_regions", "query_fused_regions_total"),
    ("region_fetches", "query_region_fetches_total"),
    ("spill_events", "query_spill_events_total"),
    ("cache_hits", "cache_hits_total"),
    ("cache_misses", "cache_misses_total"),
    ("cache_hit_bytes", "cache_hit_bytes_total"),
    ("cache_evictions", "cache_evictions_total"),
    ("cache_evict_bytes", "cache_evict_bytes_total"),
    ("faults_injected", "faults_injected_total"),
    ("transient_retries", "transient_retries_total"),
    ("retry_backoff_s", "retry_backoff_seconds_total"),
    ("fragments_recomputed", "fragments_recomputed_total"),
    ("fragments_recomputed_remote", "fragments_recomputed_remote_total"),
    ("fragments_hedged", "fragments_hedged_total"),
    ("degraded_batches", "degraded_batches_total"),
    ("peers_lost", "dcn_peers_lost_total"),
    ("partitions_reowned", "dcn_partitions_reowned_total"),
    ("queries_resubmitted", "queries_resubmitted_total"),
    ("frames_deduped", "dcn_frames_deduped_total"),
    ("quorum_losses", "dcn_quorum_losses_total"),
    ("rank_rejoins", "dcn_rank_rejoins_total"),
    ("coordinator_failovers", "dcn_coordinator_failovers_total"),
    ("integrity_failures", "integrity_failures_total"),
    ("stalls_detected", "watchdog_stalls_total"),
    ("prepared_hits", "prepared_hits_total"),
    ("prepared_misses", "prepared_misses_total"),
)

# log-bucket (base-2) histogram upper bounds in seconds: ~1 ms .. 32 s,
# then +Inf — the latency range a query service lives in
HIST_BOUNDS = tuple(2.0 ** e for e in range(-10, 6))

_PREFIX = "srt_"


class _Metric:
    __slots__ = ("name", "kind", "labels", "help", "series")

    def __init__(self, name: str, kind: str, labels: Tuple[str, ...],
                 help_: str):
        self.name = name
        self.kind = kind
        self.labels = labels
        self.help = help_
        # counter/gauge: {label-values-tuple: float}
        # histogram: {label-values-tuple: [bucket counts..., +inf, sum]}
        self.series: Dict[Tuple[str, ...], object] = {}


class _Registry:
    """The process-global registry.  Lives in utils/ deliberately: the
    whole engine may import it without cycles, and the hot entry points
    cost one attribute read when disabled."""

    def __init__(self):
        self._lock = threading.Lock()
        self.on = True
        self._metrics: Dict[str, _Metric] = {}
        for name, kind, labels, help_ in METRICS:
            self._metrics[name] = _Metric(
                name, kind, tuple(labels.split()), help_)
        self._providers: List[Callable[[], None]] = []
        # fleet view: set from DCN heartbeat replies (the coordinator's
        # per-rank merge); {} until this process joins a group
        self._fleet: Dict[str, object] = {}
        self._slo = _SloTracker()

    # -- write paths --------------------------------------------------------------
    def _labels_key(self, m: _Metric, labels: Dict[str, object]
                    ) -> Tuple[str, ...]:
        return tuple(str(labels.get(k, "")) for k in m.labels)

    def count(self, name: str, amount: float, labels: Dict[str, object]
              ) -> None:
        m = self._metrics.get(name)
        if m is None or m.kind not in ("counter", "gauge"):
            raise KeyError(f"unregistered counter {name!r} — add it to "
                           f"telemetry.METRICS")
        key = self._labels_key(m, labels)
        with self._lock:
            m.series[key] = m.series.get(key, 0.0) + amount

    def gauge_set(self, name: str, value: float,
                  labels: Dict[str, object]) -> None:
        m = self._metrics.get(name)
        if m is None or m.kind != "gauge":
            raise KeyError(f"unregistered gauge {name!r} — add it to "
                           f"telemetry.METRICS")
        key = self._labels_key(m, labels)
        with self._lock:
            m.series[key] = float(value)

    def observe(self, name: str, value: float,
                labels: Dict[str, object]) -> None:
        m = self._metrics.get(name)
        if m is None or m.kind != "histogram":
            raise KeyError(f"unregistered histogram {name!r} — add it "
                           f"to telemetry.METRICS")
        key = self._labels_key(m, labels)
        idx = bisect.bisect_left(HIST_BOUNDS, value)
        with self._lock:
            h = m.series.get(key)
            if h is None:
                h = m.series[key] = [0] * (len(HIST_BOUNDS) + 1) + [0.0]
            h[idx] += 1
            h[-1] += float(value)

    # -- read paths ---------------------------------------------------------------
    def refresh(self) -> None:
        """Run the scrape-time providers (SLO burn gauges, sync-trace
        drop gauge) OUTSIDE the registry lock — providers call the
        ordinary write paths."""
        for p in list(self._providers):
            try:
                p()
            except Exception:  # fault-ok (a broken provider must never fail a scrape)
                pass

    def copy_series(self) -> Dict[str, Tuple[_Metric, Dict]]:
        with self._lock:
            return {name: (m, {k: (list(v) if isinstance(v, list)
                                   else v)
                               for k, v in m.series.items()})
                    for name, m in self._metrics.items()}


# ---------------------------------------------------------------------------------
# SLO burn tracking
# ---------------------------------------------------------------------------------

class _SloTracker:
    """Per-tenant rolling good/bad event log feeding multi-window
    burn-rate gauges.  Events are appended at query completion (cheap:
    one deque append under a lock); burn rates are computed lazily at
    scrape time over the configured trailing windows."""

    MAX_EVENTS = 8192  # per tenant; windows are short, this is ample

    def __init__(self):
        self._lock = threading.Lock()
        self._events: Dict[str, deque] = {}
        self.latency_s = 1.0
        self.target = 0.99
        self.windows: Tuple[float, ...] = (60.0, 600.0)

    def configure(self, conf) -> None:
        with self._lock:
            self.latency_s = conf[
                "spark.rapids.tpu.server.slo.latencyMs"] / 1000.0
            self.target = conf["spark.rapids.tpu.server.slo.target"]
            wins = []
            for part in str(conf[
                    "spark.rapids.tpu.server.slo.windows"]).split(","):
                part = part.strip()
                if part:
                    wins.append(float(part))
            if wins:
                self.windows = tuple(wins)

    def observe(self, tenant: str, latency_s: float, ok: bool) -> None:
        good = ok and latency_s <= self.latency_s
        now = time.monotonic()  # span-api-ok (window bookkeeping, not span timing)
        with self._lock:
            dq = self._events.get(tenant)
            if dq is None:
                dq = self._events[tenant] = deque(maxlen=self.MAX_EVENTS)
            dq.append((now, good))
        count("slo_good_total" if good else "slo_bad_total", 1,
              tenant=tenant)

    def export(self) -> None:
        """Recompute burn-rate gauges for every tenant/window pair —
        the scrape-time provider."""
        now = time.monotonic()  # span-api-ok (window bookkeeping, not span timing)
        with self._lock:
            budget = max(1e-9, 1.0 - self.target)
            snap = {t: list(dq) for t, dq in self._events.items()}
            windows = self.windows
        for tenant, events in snap.items():
            for w in windows:
                recent = [g for (t, g) in events if now - t <= w]
                total = len(recent)
                bad = sum(1 for g in recent if not g)
                burn = (bad / total / budget) if total else 0.0
                gauge_set("slo_burn_rate", round(burn, 4),
                          tenant=tenant, window=f"{w:g}s")

    def snapshot(self) -> Dict[str, object]:
        now = time.monotonic()  # span-api-ok (window bookkeeping, not span timing)
        with self._lock:
            budget = max(1e-9, 1.0 - self.target)
            out = {"latency_ms": round(self.latency_s * 1e3, 1),
                   "target": self.target,
                   "windows_s": list(self.windows), "tenants": {}}
            snap = {t: list(dq) for t, dq in self._events.items()}
        for tenant, events in snap.items():
            per = {}
            for w in out["windows_s"]:
                recent = [g for (t, g) in events if now - t <= w]
                total = len(recent)
                bad = sum(1 for g in recent if not g)
                per[f"{w:g}s"] = {
                    "total": total, "bad": bad,
                    "burn_rate": round(bad / total / budget, 4)
                    if total else 0.0}
            out["tenants"][tenant] = per
        return out


_REG = _Registry()


# ---------------------------------------------------------------------------------
# Module API
# ---------------------------------------------------------------------------------

def enabled() -> bool:
    return _REG.on


def configure(conf) -> None:
    """Arm/disarm from the conf (called wherever an ExecContext or a
    serving component is built — runtime ``conf.set`` applies on the
    next query).  Also refreshes the SLO objectives."""
    on = conf["spark.rapids.tpu.telemetry.enabled"]
    with _REG._lock:
        _REG.on = bool(on)
    if on:
        _REG._slo.configure(conf)


def count(name: str, amount: float = 1, **labels) -> None:
    """Add to a counter (monotonic; fleet-mergeable)."""
    if not _REG.on or not amount:
        return
    _REG.count(name, amount, labels)


def gauge_set(name: str, value: float, **labels) -> None:
    """Set a gauge (rank-local; not summed into fleet rollups)."""
    if not _REG.on:
        return
    _REG.gauge_set(name, value, labels)


def observe(name: str, value: float, **labels) -> None:
    """Record one observation into a log-bucket histogram."""
    if not _REG.on:
        return
    _REG.observe(name, value, labels)


def register_provider(fn: Callable[[], None]) -> None:
    """Register a scrape-time provider: called (best-effort) before
    every render/snapshot to refresh computed gauges."""
    with _REG._lock:
        if fn not in _REG._providers:
            _REG._providers.append(fn)


def fold_query_stats(stats) -> None:
    """THE QueryStats fold-in choke point: a query scope exiting to the
    process aggregate mirrors its counts into the registry (one call
    per query, ~35 dict adds)."""
    if not _REG.on:
        return
    for field, metric in _QS_FOLD:
        v = getattr(stats, field, 0)
        if v:
            _REG.count(metric, v, {})


def slo_observe(tenant: str, latency_s: float, ok: bool) -> None:
    """Feed one completed query into the SLO burn tracker."""
    if not _REG.on:
        return
    _REG._slo.observe(tenant, latency_s, ok)


def slo_snapshot() -> Dict[str, object]:
    return _REG._slo.snapshot()


def slo_latency_s() -> float:
    """The configured SLO latency threshold in seconds — exposed so the
    flight recorder's capture decision uses EXACTLY the verdict
    ``slo_observe`` applies (the two ledgers must reconcile)."""
    return _REG._slo.latency_s


# ---------------------------------------------------------------------------------
# Scrape surfaces
# ---------------------------------------------------------------------------------

def _series_label(m: _Metric, key: Tuple[str, ...]) -> str:
    if not m.labels:
        return ""
    return ",".join(f'{k}="{v}"' for k, v in zip(m.labels, key))


def _flat_label(m: _Metric, key: Tuple[str, ...]) -> str:
    return ",".join(f"{k}={v}" for k, v in zip(m.labels, key))


def snapshot() -> Dict[str, Dict[str, object]]:
    """JSON-friendly view: {metric: {label-string: value}} (histograms
    become {"buckets": [...], "sum": s, "count": n})."""
    _REG.refresh()
    out: Dict[str, Dict[str, object]] = {}
    for name, (m, series) in sorted(_REG.copy_series().items()):
        if not series:
            continue
        entry: Dict[str, object] = {}
        for key, v in sorted(series.items()):
            lbl = _flat_label(m, key)
            if m.kind == "histogram":
                entry[lbl] = {"buckets": v[:-1], "sum": round(v[-1], 6),
                              "count": int(sum(v[:-1]))}
            else:
                entry[lbl] = round(v, 6) if isinstance(v, float) else v
        out[name] = entry
    return out


def render_prometheus() -> str:
    """Prometheus exposition text for ``/metrics``."""
    _REG.refresh()
    lines: List[str] = []
    for name, (m, series) in sorted(_REG.copy_series().items()):
        if not series:
            continue
        pname = _PREFIX + name
        lines.append(f"# HELP {pname} {m.help}")
        lines.append(f"# TYPE {pname} {m.kind}")
        for key, v in sorted(series.items()):
            lbl = _series_label(m, key)
            if m.kind == "histogram":
                cum = 0
                for bound, c in zip(HIST_BOUNDS, v[:-2]):
                    cum += c
                    sep = "," if lbl else ""
                    lines.append(
                        f'{pname}_bucket{{{lbl}{sep}le="{bound:g}"}} '
                        f'{cum}')
                cum += v[-2]
                sep = "," if lbl else ""
                lines.append(
                    f'{pname}_bucket{{{lbl}{sep}le="+Inf"}} {cum}')
                base = f"{{{lbl}}}" if lbl else ""
                lines.append(f"{pname}_sum{base} {v[-1]:g}")
                lines.append(f"{pname}_count{base} {cum}")
            else:
                base = f"{{{lbl}}}" if lbl else ""
                lines.append(f"{pname}{base} {v:g}")
    return "\n".join(lines) + "\n"


def catalog_md() -> str:
    """The metrics catalog for docs/observability.md — generated from
    METRICS the way docs/configs.md is generated from the conf
    registry, so the doc cannot drift (test-enforced two-way sync)."""
    lines = ["| Metric | Kind | Labels | Description |",
             "|---|---|---|---|"]
    for name, kind, labels, help_ in METRICS:
        lines.append(f"| {_PREFIX}{name} | {kind} | "
                     f"{labels or '-'} | {help_} |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------------
# Fleet aggregation (DCN heartbeat piggyback)
# ---------------------------------------------------------------------------------

def wire_snapshot() -> Dict[str, float]:
    """Flat cumulative view of the MERGEABLE series (counters +
    histogram buckets/sums; gauges stay rank-local): the unit the
    heartbeat delta and the coordinator merge speak."""
    out: Dict[str, float] = {}
    for name, (m, series) in _REG.copy_series().items():
        if m.kind == "gauge":
            continue
        for key, v in series.items():
            lbl = _flat_label(m, key)
            skey = f"{name}|{lbl}"
            if m.kind == "histogram":
                for i, c in enumerate(v[:-1]):
                    if c:
                        out[f"{skey}|b{i}"] = float(c)
                if v[-1]:
                    out[f"{skey}|sum"] = round(float(v[-1]), 6)
            else:
                out[skey] = round(float(v), 6)
    return out


def wire_delta(last: Dict[str, float]) -> Dict[str, float]:
    """Series whose cumulative value changed since ``last`` (the
    sender's record of what it already shipped).  Values are CUMULATIVE
    — the merge is replacement per (rank, series), so duplicated or
    re-ordered delivery cannot double-count."""
    cur = wire_snapshot()
    return {k: v for k, v in cur.items() if last.get(k) != v}


def merge_rank(ranks: Dict[int, Dict[str, float]], rank: int,
               delta: Dict[str, float]) -> None:
    """Coordinator-side merge of one rank's delta into the per-rank
    view (replacement semantics)."""
    ranks.setdefault(int(rank), {}).update(delta)


def rollup(ranks: Dict[int, Dict[str, float]]) -> Dict[str, float]:
    """Fleet rollup: sum each series across ranks."""
    out: Dict[str, float] = {}
    for series in ranks.values():
        for k, v in series.items():
            out[k] = round(out.get(k, 0.0) + v, 6)
    return out


def set_fleet(view: Dict[str, object]) -> None:
    """Adopt the coordinator's fleet view (shipped on a heartbeat
    reply): {"version", "ranks": {rank: {series: value}}, "rollup"}."""
    with _REG._lock:
        _REG._fleet = dict(view or {})


def fleet() -> Dict[str, object]:
    """The last fleet view this process saw ({} when not in a group) —
    scrapeable from ANY front door."""
    with _REG._lock:
        return dict(_REG._fleet)


# ---------------------------------------------------------------------------------
# Test support
# ---------------------------------------------------------------------------------

def reset_for_tests() -> None:
    """Zero every series and the SLO/fleet state (test isolation)."""
    with _REG._lock:
        for m in _REG._metrics.values():
            m.series.clear()
        _REG._fleet = {}
    with _REG._slo._lock:
        _REG._slo._events.clear()


register_provider(_REG._slo.export)

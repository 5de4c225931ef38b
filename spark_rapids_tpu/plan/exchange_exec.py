"""Shuffle exchange: hash repartitioning as a plan operator.

Reference: GpuShuffleExchangeExecBase.scala:266-383 (partitioned device
slicing feeding the shuffle manager) + GpuHashPartitioningBase.scala.  The
TPU redesign: partition ids are Spark-exact murmur3 (ops/hashing.py) computed
on device; rows are re-bucketed into one output batch per partition, and
every downstream operator (final aggregate, shuffled join) processes
partitions independently — the same dataflow a distributed shuffle produces,
realized in-process.  Transports (SURVEY §5.8):

  * CACHE_ONLY (this module): partitions stay device-resident in one
    process — correctness + out-of-core decomposition on a single chip;
  * ICI (parallel/exchange.py): the same bucketize feeding one
    ``lax.all_to_all`` across a jax Mesh for stage-resident multi-chip
    execution (driven by parallel/distributed.py and the multichip dryrun);
  * HOST (``_execute_host`` below): partition slices leave the device as
    compressed Arrow frame files — the same frame files the DCN tier
    (parallel/dcn.py DcnExchangeExec) serves to peers, with the same
    durable-map-output fragment recovery underneath (a lost fragment
    re-pulls from the frame files; across processes, a DEAD peer's
    fragments re-pull from the durable map output it published at
    commit).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Tuple

import jax
import numpy as np
import jax.numpy as jnp

from .. import types as T
from ..batch import ColumnBatch, DeviceColumn, Field, Schema
from ..exprs import EvalContext, Expression
from ..ops import batch_utils
from ..ops.hashing import spark_partition_id
from .physical import ExecContext, TpuExec, _cached_program, program

__all__ = ["ShuffleExchangeExec"]


def _partition_ranges(counts, target_rows: int):
    """Group whole partitions [lo, hi) into contiguous ranges of roughly
    ``target_rows`` each; returns [(lo, hi, rows)]."""
    ranges = []
    lo = 0
    acc = 0
    n = len(counts)
    for p in range(n):
        acc += int(counts[p])
        if acc >= target_rows:
            ranges.append((lo, p + 1, acc))
            lo, acc = p + 1, 0
    if lo < n:
        ranges.append((lo, n, acc))
    return ranges

_PID_FIELD = Field("__pid", T.INT32, False)
_PID_SCHEMA = Schema([_PID_FIELD])


class ShuffleExchangeExec(TpuExec):
    """Hash-repartition child output into ``n_parts`` partition batches.

    Yields exactly ``n_parts`` batches, one per partition id in order —
    downstream operators rely on that alignment (a shuffled join zips the
    two sides' partition streams pairwise).
    """

    outputs_partitions = True

    def __init__(self, child: TpuExec, key_exprs: List[Expression],
                 n_parts: int, string_dicts: Optional[dict] = None,
                 coalesce_output: bool = False):
        super().__init__([child])
        self.key_exprs = key_exprs  # bound against child.output_schema
        self.n_parts = n_parts
        # key index → StringDictionary, shared with the downstream join so
        # string keys hash via comparable codes (ops/strings.py)
        self.string_dicts = string_dicts
        # merge small partitions into target-size output batches (AQE
        # coalesced shuffle read).  Only valid when the consumer needs
        # groups-confined-to-one-batch, NOT partition alignment (final
        # aggregate yes; shuffled-join zip no).
        self.coalesce_output = coalesce_output

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def node_desc(self):
        return (f"TpuShuffleExchange hashpartitioning({len(self.key_exprs)} "
                f"keys, {self.n_parts})")

    def _pid_fn(self):
        keys = self.key_exprs
        n_parts = self.n_parts
        fp = f"exchange-pid|{n_parts}|" + "|".join(
            e.fingerprint() for e in keys)

        def build():
            @program("exchange_pid")
            def f(arrays, sel, num_rows):
                cap = next(a[0].shape[0] for a in arrays if a is not None)
                active = jnp.arange(cap, dtype=jnp.int32) < num_rows
                if sel is not None:
                    active = active & sel
                ectx = EvalContext(list(arrays), cap, active=active)
                kvs = [e.eval(ectx) for e in keys]
                pid = spark_partition_id(kvs, n_parts)
                # inactive rows park at n_parts (matches no partition)
                return jnp.where(active, pid, n_parts)
            return f

        return _cached_program(fp, build)

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        mode = ctx.conf["spark.rapids.tpu.shuffle.mode"]
        if mode == "ICI":
            # ICI exchanges execute inside a shard_map fragment
            # (parallel/spmd.py), never through this iterator path.
            # Reaching here means the fragment extraction could not lower
            # the surrounding plan — degrade only when explicitly allowed.
            if not ctx.conf["spark.rapids.tpu.shuffle.ici.fallback"]:
                raise RuntimeError(
                    "shuffle.mode=ICI: this exchange was not lowered onto "
                    "the mesh (unsupported surrounding plan); set "
                    "spark.rapids.tpu.shuffle.ici.fallback=true to run it "
                    "single-process instead")
            import logging
            logging.getLogger("spark_rapids_tpu.spmd").warning(
                "ICI exchange falling back to single-process CACHE_ONLY "
                "(shuffle.ici.fallback=true)")
        if mode == "HOST":
            yield from self._execute_host(ctx)
            return
        yield from self._execute_device_resident(ctx)

    def _execute_host(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        """Host-staged multithreaded transport: partition slices leave the
        device as compressed Arrow IPC frames; HBM holds one partition at
        a time (RapidsShuffleThreadedWriterBase analog)."""
        import numpy as _np

        from ..batch import from_arrow, to_arrow
        from ..parallel.host_shuffle import HostShuffle
        m = ctx.metric_set(self.op_id)
        pid_fn = self._pid_fn()
        shuffle = HostShuffle(
            self.n_parts,
            ctx.conf["spark.rapids.tpu.memory.spill.dir"],
            num_threads=ctx.conf[
                "spark.rapids.tpu.sql.multiThreadedRead.numThreads"],
            compress=ctx.conf["spark.rapids.tpu.shuffle.compress"])
        try:
            for batch in self.children[0].execute(ctx):
                with m.time("opTime"):
                    arrays = tuple(
                        (c.data, c.valid) if isinstance(c, DeviceColumn)
                        else None for c in batch.columns)
                    if self.string_dicts is not None:
                        from .join_exec import encode_key_arrays
                        arrays = encode_key_arrays(
                            arrays, batch, self.key_exprs,
                            self.string_dicts)
                    from ..utils.metrics import fetch as _fetch
                    pids = _fetch(pid_fn(
                        arrays, batch.sel, np.int32(batch.num_rows)))
                    t = to_arrow(batch_utils.compact(batch))
                    active_pids = pids[:batch.capacity]
                    # compact() dropped masked rows; recompute their pids
                    # on the compacted table via a host mask gather
                    keep = active_pids < self.n_parts
                    row_pids = active_pids[keep][:t.num_rows]
                for p in range(self.n_parts):
                    sub = t.filter(row_pids == p)
                    shuffle.write_partition(p, sub)
                m.add("numInputBatches", 1)
            with m.time("opTime"):
                shuffle.finish_writes()
            min_cap = ctx.conf["spark.rapids.tpu.sql.minBatchCapacity"]
            from ..faults.recovery import transient_retry
            from ..service import cancel as _cancel
            for p in range(self.n_parts):
                _cancel.check()  # shuffle reader batch boundary
                # a lost/failed fragment re-pulls the partition from the
                # producing stage's durable frame files (lineage
                # recompute) instead of failing the query; a successful
                # re-pull after a fault counts fragments_recomputed and
                # lands a 'recovered' trace mark attributed to this op
                tables = transient_retry(
                    ctx, "shuffle.fragment",
                    lambda p=p: list(shuffle.read_partition(p)),
                    desc=f"{self.op_id} part-{p:05d}",
                    recover_counter="fragments_recomputed")
                with m.time("opTime"):
                    if not tables:
                        from .join_exec import _empty_batch
                        out = _empty_batch(self.output_schema)
                    else:
                        import pyarrow as pa
                        whole = pa.concat_tables(tables)
                        out = from_arrow(whole, min_capacity=min_cap,
                                         device=ctx.device)
                m.add("numOutputRows", out.num_rows)
                m.add("numOutputBatches", 1)
                yield out
        finally:
            shuffle.close()

    def stage_input(self, ctx: "ExecContext") -> list:
        """Materialize the input as spillable handles (the shuffle's
        staging barrier), memoized: AQE-lite probes the ACTUAL staged
        size here before deciding shuffle-vs-broadcast, and the normal
        partition path reuses the same handles — the probe is never
        wasted work (GpuCustomShuffleReaderExec stats analog)."""
        if getattr(self, "_staged_raw", None) is not None:
            return self._staged_raw
        from ..memory.spill import get_catalog
        from ..service import cancel
        catalog = get_catalog(ctx.conf)
        m = ctx.metric_set(self.op_id)
        raw = []
        try:
            for batch in self.children[0].execute(ctx):
                cancel.check()  # abort staging at a batch boundary
                raw.append(catalog.register(batch, priority=0))
                m.add("numInputBatches", 1)
        except BaseException:
            # a cancelled/failed staging pass must not leak the handles
            # it already registered (assert_no_leaks after an abort)
            for h in raw:
                h.close()
            raise
        self._staged_raw = raw
        return raw

    def staged_fits(self, ctx, threshold: int) -> bool:
        """Does the staged input's LIVE byte size fit under
        ``threshold``?  Two phases: a handle-METADATA row bound first
        (no unspill, no sync — num_rows bounds live rows), and only
        when the bound exceeds the threshold are selection masks
        resolved (h.get() + ONE batched fetch) for the exact count —
        the mis-estimated-filter case AQE exists for."""
        import jax.numpy as jnp

        from ..batch import estimated_row_bytes
        from ..utils.metrics import fetch
        raw = self.stage_input(ctx)
        width = estimated_row_bytes(self.output_schema)
        bound_rows = sum(h.num_rows for h in raw)
        if bound_rows * width <= threshold:
            return True
        total_rows = 0
        pending = []
        for h in raw:
            b = h.get()
            if b.sel is None:
                total_rows += b.num_rows
            else:
                pending.append(jnp.sum(b.active_mask()))
        if pending:
            total_rows += sum(int(x) for x in fetch(pending))
        return total_rows * width <= threshold

    def _execute_device_resident(self, ctx: ExecContext
                                 ) -> Iterator[ColumnBatch]:
        from ..memory.spill import get_catalog
        m = ctx.metric_set(self.op_id)
        pid_fn = self._pid_fn()
        catalog = get_catalog(ctx.conf)
        # staging is the shuffle's materialization barrier: every staged
        # batch is registered spillable (ShuffleBufferCatalog analog) so
        # memory pressure during a long upstream can evict them to host
        staged = []
        raw = self.stage_input(ctx)
        try:

            if self.coalesce_output and raw:
                # whole shuffle fits one output batch: partitioning would
                # only split and re-merge — skip pids entirely (the
                # consumer needs groups-confined-to-one-batch, which a
                # single batch satisfies trivially).  Handle metadata, NOT
                # get(): probing must not unspill every staged batch.
                total = sum(h.num_rows for h in raw)
                batch_rows_ = ctx.conf["spark.rapids.tpu.sql.batchSizeRows"]
                if total <= batch_rows_:
                    with m.time("opTime"):
                        if len(raw) == 1:
                            out = raw[0].get()
                        else:
                            out = batch_utils.concat_batches(
                                [h.get() for h in raw])
                        if out.sel is not None and \
                                getattr(out, "bound", None) is None:
                            # unbounded masked batch: normalize capacity
                            # (one sync).  Bounded producers (grid aggs)
                            # already sliced small — pass the mask through
                            # sync-free; the consumer applies it.
                            out = batch_utils.compact(out)
                    m.add("numOutputRows", out.num_rows)
                    m.add("numOutputBatches", 1)
                    yield out
                    return

            from ..utils import tracing
            from ..utils.metrics import QueryStats
            for bh in raw:
                batch = bh.get()
                nbytes = batch.device_size_bytes()
                QueryStats.get().shuffle_bytes += nbytes
                tracing.mark(self.op_id, "shuffle:stage", "shuffle",
                             bytes=nbytes, rows=batch.num_rows)
                with m.time("opTime"):
                    arrays = tuple(
                        (c.data, c.valid) if isinstance(c, DeviceColumn)
                        else None for c in batch.columns)
                    if self.string_dicts is not None:
                        from .join_exec import encode_key_arrays
                        arrays = encode_key_arrays(
                            arrays, batch, self.key_exprs, self.string_dicts)
                    pids = pid_fn(arrays, batch.sel,
                                  np.int32(batch.num_rows))
                staged.append((bh, catalog.register(ColumnBatch(
                    _PID_SCHEMA, [DeviceColumn(
                        _PID_FIELD.dtype, pids)],
                    batch.num_rows), priority=0)))
            if not staged:
                # the exactly-n_parts contract holds even for empty input
                # (the shuffled-join zip relies on it)
                from .join_exec import _empty_batch
                for _ in range(self.n_parts):
                    yield _empty_batch(self.output_schema)
                return
            batch_rows = ctx.conf["spark.rapids.tpu.sql.batchSizeRows"]
            # one host fetch of per-partition row counts: every partition's
            # compact then shares ONE output capacity bucket, so the gather
            # program compiles once instead of once per partition size (a
            # remote-TPU compile costs seconds; there are n_parts of them)
            with m.time("opTime"):
                from ..utils.metrics import fetch as _fetch
                counts = np.zeros(self.n_parts + 1, dtype=np.int64)
                pid_hosts = _fetch([ph.get().columns[0].data
                                    for _, ph in staged])
                for pid_data in pid_hosts:
                    counts += np.bincount(
                        pid_data, minlength=self.n_parts + 1
                    )[: self.n_parts + 1]
            shared_cap = max(1, int(counts[: self.n_parts].max(initial=0)))

            if self.coalesce_output:
                # AQE coalesced shuffle read, range form: group WHOLE
                # partitions into count-balanced contiguous ranges and
                # emit one compact per OUTPUT batch — a tiny shuffle (the
                # common partial-agg case) becomes a single device gather
                # instead of n_parts of them (each eager op is its own
                # dispatch)
                ranges = _partition_ranges(counts[: self.n_parts],
                                           batch_rows)
                emitted = 0
                for lo, hi, range_rows in ranges:
                    if range_rows == 0:
                        continue
                    parts = []
                    for bh, ph in staged:
                        batch = bh.get()
                        pids = ph.get().columns[0].data
                        sel = (pids >= lo) & (pids < hi)
                        parts.append(ColumnBatch(
                            batch.schema, batch.columns, batch.num_rows,
                            sel))
                    with m.time("opTime"):
                        out = batch_utils.compact(
                            parts[0] if len(parts) == 1 else
                            batch_utils.concat_batches(parts))
                    m.add("numOutputRows", out.num_rows)
                    m.add("numOutputBatches", 1)
                    emitted += 1
                    yield out
                if emitted == 0:
                    from .join_exec import _empty_batch
                    yield _empty_batch(self.output_schema)
                return

            from ..service import cancel as _cancel
            for p in range(self.n_parts):
                _cancel.check()  # shuffle reader batch boundary
                parts = []
                for bh, ph in staged:
                    batch = bh.get()
                    pids = ph.get().columns[0].data
                    sel = pids == p
                    parts.append(ColumnBatch(batch.schema, batch.columns,
                                             batch.num_rows, sel))
                with m.time("opTime"):
                    if len(parts) == 1:
                        out = batch_utils.compact(parts[0],
                                                  min_capacity=shared_cap)
                    else:
                        out = batch_utils.compact(
                            batch_utils.concat_batches(parts),
                            min_capacity=shared_cap)
                m.add("numOutputRows", out.num_rows)
                m.add("numOutputBatches", 1)
                yield out
        finally:
            for _bh, ph in staged:
                ph.close()
            for bh in raw:  # staged bh handles are members of raw
                bh.close()

"""Additional physical operators: sort, limit, union, range, expand.

References: GpuSortExec.scala:86 (sort; the out-of-core variant :242 arrives
with the spill framework), limit.scala (GpuLocalLimit/GpuGlobalLimit),
basicPhysicalOperators.scala:1096 (GpuRangeExec), GpuExpandExec.scala.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..batch import ColumnBatch, DeviceColumn, Field, HostStringColumn, Schema
from ..exprs import EvalContext, Expression
from ..ops import batch_utils, groupby
from ..ops.strings import key_view
from ..utils.metrics import upload
from .physical import ExecContext, TpuExec, _cached_program, program

__all__ = ["SortExec", "LimitExec", "UnionExec", "RangeExec", "ExpandExec",
           "plan_join"]


# a string-keyed sort of at most this many rows runs on the host
# (SortExec._sort_on_host has the two prices)
_HOST_SORT_ROWS = 4096


class SortExec(TpuExec):
    """Global sort: in-core for small inputs, out-of-core for large ones.

    In-core (GpuSortExec.scala:86): concatenate, sort once on device.
    Out-of-core (GpuSortExec.scala:242 GpuOutOfCoreSortIterator +
    GpuRangePartitioner redesigned for TPU): each input batch is sorted into
    a spillable run; range boundaries are sampled from the runs' primary
    keys; each range then gathers one *contiguous slice per run* (runs are
    sorted, so slice bounds come from two searchsorted calls), concatenates
    and sorts only that range — peak HBM is one range plus whatever runs the
    spill catalog keeps resident.  Output batches emit in global order.
    """

    # a sort consumes ALL input before emitting — a pipeline breaker, and
    # therefore a region boundary for the fusion planner (plan/fusion.py)
    region_fusible = False

    def __init__(self, child: TpuExec,
                 orders: List[Tuple[Expression, bool, bool]]):
        super().__init__([child])
        self.orders = orders

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def node_desc(self):
        return f"TpuSort [{len(self.orders)} keys]"

    def child_coalesce_goal(self, i, conf):
        # fewer, larger sorted runs -> fewer range slices to merge
        from .coalesce import TargetSize
        return TargetSize(conf["spark.rapids.tpu.sql.batchSizeRows"])

    def _order_tuples(self):
        key_exprs = tuple(e for e, _, _ in self.orders)
        desc = tuple(not asc for _, asc, _ in self.orders)
        nf = tuple(n for _, _, n in self.orders)
        return key_exprs, desc, nf

    def _string_key_ordinals(self) -> List[int]:
        from .planner import string_key_ordinals
        return string_key_ordinals([e for e, _, _ in self.orders])

    def _sort_on_host(self, batch: ColumnBatch, device) -> ColumnBatch:
        """A few rows under a string key, ordered on the host, where the
        strings are and the answer is going.  It is what such a sort cost
        while string keys fell back to the CPU (the top 100 of TPC-DS Q42's
        nine groups by i_category: 0.128 s a query that way, 0.156 through
        the device's passes, gathers and their dispatches; my chip runs, PR
        33); past ``_HOST_SORT_ROWS`` the rows stay on the device (Q89's
        50,000)."""
        from ..batch import from_arrow, to_arrow
        from ..cpu.exec import sort_table
        return from_arrow(sort_table(to_arrow(batch), batch.schema,
                                     self.orders), device=device)

    def _sort_input(self, b: ColumnBatch, device) -> ColumnBatch:
        """One input batch in order.  A string-keyed sort takes a batch of
        small CAPACITY to the host as it stands: ``to_arrow`` reads its
        selection in the fetch it makes anyway, where compacting first
        costs a fetch of its own for the live count."""
        if b.capacity <= _HOST_SORT_ROWS and self._string_key_ordinals():
            return self._sort_on_host(b, device)
        return self._sort_batch(batch_utils.compact(b), device)

    def _sort_batch(self, whole: ColumnBatch, device=None) -> ColumnBatch:
        """``whole`` (compact: ``num_rows`` live rows in front) in order."""
        key_exprs, desc, nf = self._order_tuples()
        strings = self._string_key_ordinals()
        if strings and whole.num_rows <= _HOST_SORT_ROWS:
            return self._sort_on_host(whole, device)
        # string keys sort as the ranks of their strings among the batch's
        # distinct values (ops/strings.key_view): taken from THIS batch, so
        # every call orders the rows it is given, whatever dictionary they
        # came with
        keyed = key_view(whole, strings, True, device)
        arrays = tuple(
            (c.data, c.valid) if isinstance(c, DeviceColumn) else None
            for c in keyed.columns)
        # a string key is new to the device: its sort takes the form that
        # compiles by the pass (TPC-DS Q89's top-k, one float and one
        # string key, compiled for 136 s as one lexsort at 65,536 rows)
        perm = _sort_perm(key_exprs, desc, nf, bool(strings))(
            arrays, jnp.int32(whole.num_rows))
        return batch_utils.gather(whole, perm, whole.num_rows)

    def _range_key(self, batch: ColumnBatch) -> np.ndarray:
        """Host copy of the PRIMARY sort key as a totally-ordered int/float
        view (ascending in output order), for range boundary search."""
        key_exprs, desc, nf = self._order_tuples()
        fn = _range_key_fn(key_exprs[0], desc[0], nf[0])
        arrays = tuple(
            (c.data, c.valid) if isinstance(c, DeviceColumn) else None
            for c in batch.columns)
        from ..utils.metrics import fetch as _fetch
        return _fetch(fn(arrays))[: batch.num_rows]

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        from ..memory.retry import with_retry
        from ..memory.spill import get_catalog
        m = ctx.metric_set(self.op_id)
        batch_rows = ctx.conf["spark.rapids.tpu.sql.batchSizeRows"]
        catalog = get_catalog(ctx.conf)

        from ..runtime.pipeline import effective_depth, pipeline_batches
        runs = []  # spillable sorted runs
        total = 0
        try:
            # upstream decode/upload stages ahead while this run-sort's
            # XLA programs are in flight (depth 0 = serial)
            for batch in pipeline_batches(self.children[0].execute(ctx),
                                          effective_depth(ctx),
                                          label=self.op_id):
                with m.time("opTime"):
                    for srt_b in with_retry(
                            ctx, batch,
                            lambda b: self._sort_input(b, ctx.device)):
                        if srt_b.num_rows == 0:
                            continue
                        total += srt_b.num_rows
                        runs.append(catalog.register(srt_b, priority=2))
            if not runs:
                return
            if len(runs) == 1 or total <= batch_rows \
                    or self._string_key_ordinals():
                # in-core: one more sort over the concatenation (always,
                # under a string key: its ranks hold within one batch, so
                # runs cannot be cut at a common boundary)
                with m.time("opTime"):
                    whole = batch_utils.compact(batch_utils.concat_batches(
                        [h.get() for h in runs])) \
                        if len(runs) > 1 else runs[0].get()
                    out = self._sort_batch(whole, ctx.device) \
                        if len(runs) > 1 else whole
                m.add("numOutputRows", out.num_rows)
                yield out
                return
            # ---- out-of-core: range-partitioned merge ----
            n_ranges = max(2, -(-total // batch_rows))
            keys = []
            for h in runs:
                keys.append(self._range_key(h.get()))
                # don't let the key-sampling sweep pin every run in HBM
                catalog.ensure_budget()
            bounds = _sample_bounds(keys, n_ranges)
            for lo_b, hi_b in bounds:
                slices = []
                for h, rk in zip(runs, keys):
                    lo = 0 if lo_b is None else int(
                        np.searchsorted(rk, lo_b, side="left"))
                    hi = len(rk) if hi_b is None else int(
                        np.searchsorted(rk, hi_b, side="left"))
                    if hi > lo:
                        slices.append(batch_utils.slice_batch(
                            h.get(), lo, hi - lo))
                if not slices:
                    continue
                with m.time("opTime"):
                    part = batch_utils.compact(
                        batch_utils.concat_batches(slices)) \
                        if len(slices) > 1 else slices[0]
                    del slices
                    # plain retry only: splitting a range would interleave
                    # the globally-ordered output
                    outs = list(with_retry(
                        ctx, part,
                        lambda b: self._sort_batch(b, ctx.device),
                        split=None))
                for out in outs:
                    m.add("numOutputRows", out.num_rows)
                    yield out
        finally:
            for h in runs:
                h.close()


def _range_key_fn(key_expr, desc: bool, nulls_first: bool):
    """Jitted primary-key view: int-valued, ascending in OUTPUT order
    (desc flip + null placement folded in), for range boundary searches."""
    from .physical import _cached_program
    fp = f"rangekey|{key_expr.fingerprint()}|{desc}|{nulls_first}"

    def build():
        @program("sort_range_key")
        def f(arrays):
            cap = next(a[0].shape[0] for a in arrays if a is not None)
            active = jnp.ones((cap,), dtype=bool)
            ectx = EvalContext(list(arrays), cap, active=active)
            d, v = key_expr.eval(ectx)
            if d.ndim == 2:
                # wide decimal: the hi limb is a monotonic coarse image
                # of the 128-bit value — valid for range partitioning
                # (ties collapse into one range; the in-range sort is
                # exact)
                d = d[:, 1]
            view = groupby.sortable_view(d)
            if desc:
                view = ~view
            if v is not None:
                info = jnp.iinfo(view.dtype)
                sent = info.min if nulls_first else info.max
                view = jnp.where(v, view, sent)
            return view
        return f

    return _cached_program(fp, build)


def _sample_bounds(keys: List[np.ndarray], n_ranges: int):
    """Range boundaries from per-run key samples (GpuRangePartitioner
    sampling analog).  Returns [(lo, hi), ...] with None for open ends."""
    samples = []
    for k in keys:
        if len(k) == 0:
            continue
        step = max(1, len(k) // 64)
        samples.append(k[::step])
    if not samples:
        return [(None, None)]
    s = np.sort(np.concatenate(samples))
    cuts = []
    for i in range(1, n_ranges):
        q = s[min(len(s) - 1, (len(s) * i) // n_ranges)]
        if not cuts or q > cuts[-1]:
            cuts.append(q)
    bounds = []
    prev = None
    for c in cuts:
        bounds.append((prev, c))
        prev = c
    bounds.append((prev, None))
    return bounds


def _sort_perm(key_exprs, desc, nf, passes: bool = False):
    from .physical import _cached_program
    fp = "|".join(e.fingerprint() for e in key_exprs) + str(desc) + str(nf) \
        + ("|passes" if passes else "")

    def build():
        @program("sort")
        def f(arrays, num_rows):
            cap = next(a[0].shape[0] for a in arrays if a is not None)
            active = jnp.arange(cap, dtype=jnp.int32) < num_rows
            ectx = EvalContext(list(arrays), cap, active=active)
            keys = [e.eval(ectx) for e in key_exprs]
            return groupby.sort_indices_for_keys(keys, active, desc, nf,
                                                 passes=passes)
        return f

    return _cached_program("sort|" + fp, build)


class TopKExec(SortExec):
    """TakeOrderedAndProject analog (limit.scala GpuTopN): a running top-k
    kept on device.  Each input batch is sorted and clipped to k rows, then
    merged (concat → sort → clip) into the running buffer — so peak HBM is
    one batch plus k rows, never the whole input, and every step is a
    static-shape XLA program.  ``offset`` rows are dropped at the end
    (Spark's Limit-with-offset on sorted input)."""

    def __init__(self, child: TpuExec,
                 orders: List[Tuple[Expression, bool, bool]],
                 n: int, offset: int = 0):
        super().__init__(child, orders)
        self.n = n
        self.offset = offset

    def node_desc(self):
        return (f"TpuTopK {self.n} [{len(self.orders)} keys]"
                + (f" offset {self.offset}" if self.offset else ""))

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        from ..memory.retry import with_retry
        from ..runtime.pipeline import effective_depth, pipeline_batches
        m = ctx.metric_set(self.op_id)
        k = self.n + self.offset
        top: ColumnBatch = None

        def _clip(b: ColumnBatch) -> ColumnBatch:
            return batch_utils.slice_batch(b, 0, min(k, b.num_rows)) \
                if b.num_rows > k else b

        for batch in pipeline_batches(self.children[0].execute(ctx),
                                      effective_depth(ctx),
                                      label=self.op_id):
            with m.time("opTime"):
                for srt in with_retry(
                        ctx, batch,
                        lambda b: _clip(self._sort_input(b, ctx.device))):
                    if srt.num_rows == 0:
                        continue
                    if top is None:
                        top = srt
                    else:
                        merged = batch_utils.compact(
                            batch_utils.concat_batches([top, srt]))
                        top = _clip(self._sort_batch(merged, ctx.device))
        if top is None:
            return
        take = top.num_rows - self.offset
        if take <= 0:
            return
        if self.offset > 0:
            top = batch_utils.slice_batch(top, self.offset, take)
        m.add("numOutputRows", top.num_rows)
        yield top


class SampleExec(TpuExec):
    """Bernoulli sample (GpuSampleExec, basicPhysicalOperators.scala Sample):
    a per-row uniform draw folded into the batch's selection mask — zero
    data movement, the mask fuses into whatever consumes the batch."""

    def __init__(self, child: TpuExec, fraction: float, seed: int):
        super().__init__([child])
        self.fraction = float(fraction)
        self.seed = int(seed)

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def node_desc(self):
        return f"TpuSample {self.fraction} seed={self.seed}"

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        m = ctx.metric_set(self.op_id)
        for idx, batch in enumerate(self.children[0].execute(ctx)):
            with m.time("opTime"):
                key = jax.random.fold_in(
                    jax.random.PRNGKey(self.seed), idx)
                u = jax.random.uniform(key, (batch.capacity,))
                keep = u < self.fraction
                sel = keep if batch.sel is None else (batch.sel & keep)
            yield ColumnBatch(batch.schema, batch.columns,
                              batch.num_rows, sel=sel)


class CacheExec(TpuExec):
    """First run materializes the child into spillable handles owned by the
    logical Cache node; later runs replay them (GpuInMemoryTableScanExec +
    ParquetCachedBatchSerializer analog, device-resident instead of
    parquet-encoded)."""

    def __init__(self, child: TpuExec, cache_node):
        super().__init__([child])
        self.cache_node = cache_node

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def node_desc(self):
        return self.cache_node.node_desc()

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        from ..memory.spill import get_catalog
        node = self.cache_node
        with node.lock:
            if node.materialized is None:
                catalog = get_catalog(ctx.conf)
                handles = []
                for b in self.children[0].execute(ctx):
                    handles.append(catalog.register(
                        batch_utils.compact(b), priority=1))
                node.materialized = handles
        for h in node.materialized:
            yield h.get()


class LimitExec(TpuExec):
    def __init__(self, child: TpuExec, n: int, offset: int = 0):
        super().__init__([child])
        self.n = n
        self.offset = offset

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def node_desc(self):
        return f"TpuGlobalLimit {self.n}" + (
            f" offset {self.offset}" if self.offset else "")

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        to_skip = self.offset
        to_take = self.n
        for batch in self.children[0].execute(ctx):
            if to_take <= 0:
                break
            b = batch_utils.compact(batch)
            start = min(to_skip, b.num_rows)
            to_skip -= start
            avail = b.num_rows - start
            if avail <= 0:
                continue
            take = min(avail, to_take)
            if start > 0 or take < b.num_rows:
                b = batch_utils.slice_batch(b, start, take)
            to_take -= take
            yield b


class UnionExec(TpuExec):
    # multi-input streaming: no single streaming spine for a region to
    # follow, so the union itself stays a boundary (its branches fuse
    # independently below it)
    region_fusible = False

    def __init__(self, children: List[TpuExec]):
        super().__init__(children)

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        for c in self.children:
            yield from c.execute(ctx)


class RangeExec(TpuExec):
    # leaf device source with no host syncs: fuses like ScanExec
    region_fusible = True

    def __init__(self, start: int, end: int, step: int, batch_rows: int):
        super().__init__()
        self.start, self.end, self.step = start, end, step
        self.batch_rows = batch_rows
        self._schema = Schema([Field("id", T.INT64, False)])

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def node_desc(self):
        return f"TpuRange ({self.start}, {self.end}, {self.step})"

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        total = max(0, -(-(self.end - self.start) // self.step))
        pos = 0
        while pos < total:
            n = min(self.batch_rows, total - pos)
            from ..batch import bucket_capacity
            cap = bucket_capacity(n, ctx.conf["spark.rapids.tpu.sql.minBatchCapacity"])
            ids = (self.start + (pos + jnp.arange(cap, dtype=jnp.int64))
                   * self.step)
            yield ColumnBatch(self._schema,
                              [DeviceColumn(T.INT64, ids)], n)
            pos += n


class GenerateExec(TpuExec):
    """Device explode: arrow list offsets become a parent-row gather.

    Reference: GpuGenerateExec (GpuGenerateExec.scala) — cudf's explode is
    a gather by parent row index plus the flattened child column.  Same
    shape here: the ARRAY column rides as a host arrow column whose offsets
    yield (a) the flattened element values, uploaded once, and (b) the
    parent row index per output row; every other device column is gathered
    by parent index in ONE jitted program per schema.  ``outer`` keeps
    empty/null arrays as a single null-element row (OUTER EXPLODE).
    """

    def __init__(self, child: TpuExec, column: str, out_name: str,
                 outer: bool, out_schema: Schema):
        super().__init__([child])
        self.column = column
        self.out_name = out_name
        self.outer = outer
        self._schema = out_schema
        self._ordinal = child.output_schema.index_of(column)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def node_desc(self):
        kind = "explode_outer" if self.outer else "explode"
        return f"TpuGenerate {kind}({self.column}) as {self.out_name}"

    def _gather_fn(self, in_schema: Schema):
        from .physical import _cached_program
        ordinal = self._ordinal
        dts = ",".join(f"{i}:{f.dtype}" for i, f in enumerate(in_schema)
                       if i != ordinal)
        fp = f"generate-gather|{ordinal}|{dts}"

        def build():
            @program("generate_gather")
            def f(arrays, parent):
                out = []
                for a in arrays:
                    if a is None:
                        out.append(None)
                        continue
                    d, v = a
                    out.append((d[parent],
                                None if v is None else v[parent]))
                return tuple(out)
            return f

        return _cached_program(fp, build)

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        import pyarrow as pa
        import pyarrow.compute as pc
        m = ctx.metric_set(self.op_id)
        in_schema = self.children[0].output_schema
        elem_dt = in_schema.fields[self._ordinal].dtype.element
        gather = self._gather_fn(in_schema)
        from ..batch import bucket_capacity
        for batch in self.children[0].execute(ctx):
            with m.time("opTime"):
                b = batch_utils.compact(batch)
                n = b.num_rows
                arr = b.columns[self._ordinal].array.slice(0, n)
                arr = arr.combine_chunks() if isinstance(
                    arr, pa.ChunkedArray) else arr
                lens = np.asarray(pc.list_value_length(arr)
                                  .fill_null(0)).astype(np.int64)
                if self.outer:
                    out_lens = np.maximum(lens, 1)
                    # injected rows (empty/null array) carry a null element
                    injected = lens == 0
                else:
                    out_lens = lens
                    injected = None
                total = int(out_lens.sum())
                if total == 0:
                    continue
                parent_all = np.repeat(np.arange(n, dtype=np.int64),
                                       out_lens)
                flat = arr.flatten()  # drops null/empty lists entirely
                elem_valid = np.ones(total, dtype=bool)
                if injected is not None and injected.any():
                    first_out = np.zeros(n, dtype=np.int64)
                    first_out[1:] = np.cumsum(out_lens)[:-1]
                    elem_valid[first_out[injected]] = False
                vals = np.zeros(total, dtype=elem_dt.numpy_dtype)
                slots = np.flatnonzero(elem_valid)
                if flat.null_count:
                    from ..batch import zero_scalar
                    fv = ~np.asarray(flat.is_null())
                    elem_valid[slots] = fv
                    flat = flat.fill_null(zero_scalar(flat.type))
                if elem_dt.is_floating:
                    npf = flat.to_numpy(zero_copy_only=False)
                else:  # int/bool/date/timestamp: physical int via arrow cast
                    width = pa.int64() \
                        if np.dtype(elem_dt.numpy_dtype).itemsize == 8 \
                        else pa.int32()
                    npf = flat.cast(width).to_numpy(zero_copy_only=False)
                vals[slots] = np.asarray(npf).astype(elem_dt.numpy_dtype)

                # split oversized output into batch-size chunks: total is
                # unbounded (sum of list lengths) and must not become one
                # giant device allocation (GpuGenerateExec splits too)
                batch_rows = ctx.conf["spark.rapids.tpu.sql.batchSizeRows"]
                min_cap = ctx.conf["spark.rapids.tpu.sql.minBatchCapacity"]
                arrays = tuple(
                    None if isinstance(c, HostStringColumn)
                    else (c.data, c.valid) for c in b.columns)
                outs = []
                for lo in range(0, total, batch_rows):
                    hi = min(lo + batch_rows, total)
                    m_rows = hi - lo
                    cap = bucket_capacity(m_rows, min_cap)
                    pad = cap - m_rows
                    parent = parent_all[lo:hi]
                    parent_pad = np.concatenate(
                        [parent, np.zeros(pad, np.int64)]) if pad \
                        else parent
                    gathered = gather(arrays, jnp.asarray(parent_pad))
                    cols: List = []
                    for i, f in enumerate(self._schema):
                        if i == self._ordinal:
                            data = np.zeros(cap,
                                            dtype=elem_dt.numpy_dtype)
                            data[:m_rows] = vals[lo:hi]
                            validp = np.zeros(cap, dtype=bool)
                            validp[:m_rows] = elem_valid[lo:hi]
                            cols.append(DeviceColumn(
                                elem_dt,
                                *upload((data, validp), ctx.device)))
                        elif gathered[i] is None:
                            taken = b.columns[i].array.slice(0, n).take(
                                pa.array(parent_pad))
                            cols.append(HostStringColumn(taken,
                                                         capacity=cap))
                        else:
                            d, v = gathered[i]
                            cols.append(DeviceColumn(
                                in_schema.fields[i].dtype, d, v))
                    outs.append(ColumnBatch(self._schema, cols, m_rows))
            for out in outs:
                m.add("numOutputRows", out.num_rows)
                m.add("numOutputBatches", 1)
                yield out


class ExpandExec(TpuExec):
    """Emit one projected batch per projection per input batch
    (grouping sets — GpuExpandExec.scala).

    A string column is host-carried or dictionary-coded, so a projection
    cannot compute it: it either passes the column through or, for a key
    outside the grouping set, puts NULLs in its place: the same dictionary
    with no valid row where the column is a ``DictStringColumn`` (the
    aggregate above then reads one dictionary in every batch), a host
    column of nulls otherwise."""

    # pure-device batch-in/batches-out streaming: region-safe
    region_fusible = True

    def __init__(self, child: TpuExec, projections, out_schema: Schema):
        super().__init__([child])
        self.projections = projections
        self._schema = out_schema
        # output position -> the child's column a NULL string stands in for
        self._string_src = {}
        for triples in projections:
            for j, (_name, _e, host_src) in enumerate(triples):
                if host_src is not None:
                    self._string_src.setdefault(j, host_src)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def node_desc(self):
        return f"TpuExpand [{len(self.projections)} projections]"

    def _strings(self, batch: ColumnBatch, j: int, host_src, memo: dict):
        """Output column ``j`` of one projection: the child's string
        column ``host_src`` passed through, or (None) NULLs in its place."""
        from ..batch import DictStringColumn
        passed = host_src is not None
        src = host_src if passed else self._string_src.get(j)
        like = batch.columns[src] if src is not None else None
        cap = batch.capacity
        if isinstance(like, DictStringColumn):
            if passed and like.valid is not None:
                return like
            # made FROM the column's codes, so that they sit where it sits:
            # an array committed to the device and one that is not are
            # different arguments to jit, and a different program each
            if passed:  # a validity mask all the same: see _with_validity
                if "ones" not in memo:
                    memo["ones"] = jnp.ones_like(like.codes, dtype=bool)
                return DictStringColumn(like.codes, memo["ones"],
                                        like.dictionary)
            if "dev" not in memo:
                memo["dev"] = (jnp.zeros_like(like.codes, dtype=jnp.int32),
                               jnp.zeros_like(like.codes, dtype=bool))
            return DictStringColumn(*memo["dev"], like.dictionary)
        if passed:
            return like
        if "host" not in memo:
            import pyarrow as pa
            memo["host"] = HostStringColumn(pa.nulls(cap, type=pa.string()))
        return memo["host"]

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        from ..utils.metrics import counted_span
        m = ctx.metric_set(self.op_id)

        def proj_fn(pi: int):
            # what the device computes: not the strings (class docstring)
            exprs = tuple(
                None if e is None or f_.dtype.is_string else e
                for (_n, e, _h), f_ in zip(self.projections[pi],
                                           self._schema))

            def build():
                @program("expand_project")
                def f(arrays, sel, num_rows):
                    cap = next(a[0].shape[0] for a in arrays
                               if a is not None)
                    active = jnp.arange(cap, dtype=jnp.int32) < num_rows
                    if sel is not None:
                        active = active & sel
                    ectx = EvalContext(list(arrays), cap, active=active)
                    return tuple(None if e is None else _with_validity(
                        e.eval(ectx), cap) for e in exprs), active
                return f

            return _cached_program(
                "expand|" + "|".join("-" if e is None else e.fingerprint()
                                     for e in exprs), build)

        fns = [proj_fn(pi) for pi in range(len(self.projections))]
        for batch in self.children[0].execute(ctx):
            arrays = tuple(
                (c.data, c.valid) if isinstance(c, DeviceColumn) else None
                for c in batch.columns)
            nulls: dict = {}
            for pi in range(len(self.projections)):
                with counted_span("expand_exec_s", self.op_id,
                                  "expand:project", "expand") as stats, \
                        m.time("opTime"):
                    outs, active = fns[pi](arrays, batch.sel,
                                           jnp.int32(batch.num_rows))
                    cols = []
                    for j, (f_, val, (name, e, host_src)) in enumerate(
                            zip(self._schema, outs, self.projections[pi])):
                        if val is None:
                            cols.append(self._strings(batch, j, host_src,
                                                      nulls))
                        else:
                            cols.append(
                                DeviceColumn(f_.dtype, val[0], val[1]))
                stats.expand_slot_rows += batch.capacity
                yield ColumnBatch(self._schema, cols, batch.num_rows, active)


def _with_validity(value, cap: int):
    """Every projection of an Expand hands on the same tree of arrays,
    validity included, whether its grouping set keeps a key or NULLs it:
    the aggregate above then compiles one program for all of them, not
    one a grouping set (nine for TPC-DS Q67)."""
    data, valid = value
    return data, (jnp.ones((cap,), dtype=bool) if valid is None else valid)


def plan_join(plan, left: TpuExec, right: TpuExec, conf):
    """Shuffled join: hash-partition both sides on the (common-type-promoted)
    join keys so each partition pair joins independently
    (GpuShuffledHashJoinExec.scala:90 dataflow); cross joins and disabled
    exchange fall through to the single-stream join."""
    from ..exprs import Cast
    from .exchange_exec import ShuffleExchangeExec
    from .join_exec import (SortMergeJoinExec, bound_join_keys,
                            plan_broadcast_join)
    # one dictionary registry per key index shared by both sides' exchanges
    # AND the join kernel: string-key codes must be comparable everywhere
    shared_dicts: dict = {}
    bc = plan_broadcast_join(plan, left, right, conf, shared_dicts)
    if bc is not None:
        return bc
    if (plan.how != "cross" and plan.left_keys
            and conf["spark.rapids.tpu.sql.exchange.enabled"]):
        lk, rk, common = bound_join_keys(plan, left.output_schema,
                                         right.output_schema)

        def promoted(keys):
            return [k if k.dtype == ct else Cast(k, ct)
                    for k, ct in zip(keys, common)]
        n_parts = conf["spark.rapids.tpu.sql.shuffle.partitions"]
        left = ShuffleExchangeExec(left, promoted(lk), n_parts,
                                   string_dicts=shared_dicts)
        right = ShuffleExchangeExec(right, promoted(rk), n_parts,
                                    string_dicts=shared_dicts)
    return SortMergeJoinExec(plan, left, right, conf,
                             string_dicts=shared_dicts)

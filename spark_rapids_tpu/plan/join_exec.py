"""Device joins: sort-based equi-join for the TPU.

Reference: GpuHashJoin.scala:104-383 (cuDF gather-map hash joins),
GpuShuffledHashJoinExec.scala:90, GpuBroadcastHashJoinExecBase.scala.  Device
hash tables are a poor fit for XLA (SURVEY §7.3 prescribes sort-based joins
on TPU), so the algorithm here is:

  1. evaluate join keys on both sides, promoted to a common type;
  2. union group ids and every probe row's match range [lo, lo + matches)
     over the build side sorted by id (``ops/join.match_ranges``; nulls
     never match, as in SQL equi-join);
  3. semi/anti joins finish here as a selection mask (no data movement);
     inner/outer joins compute per-row output counts, sync ONCE to learn the
     total, and run a static-shape **expansion gather** at that capacity
     (``ops/join.expand_pairs``), with unmatched outer rows emitting nulls.

The kernels of steps 2 and 3 live in ``ops/join.py``, which the mesh's
shuffled join (``parallel/spmd.py``) traces too.

Every compiled program is cached by structural fingerprint + shape bucket, so
repeated joins of the same shape reuse executables (SURVEY §7.2).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..batch import (ColumnBatch, DeviceColumn, DictStringColumn, Field,
                     HostStringColumn, Schema, bucket_capacity)
from ..exprs import EvalContext, Expression, promote_physical
from ..ops import batch_utils
from ..ops.join import (expand_pairs, match_ranges, rows_ok,
                        unmatched_build)
from ..utils.metrics import QueryStats, counted_span, current_region, \
    fetch, region_scalars, stage_scalars
from .physical import ExecContext, TpuExec, _cached_program, program

__all__ = ["SortMergeJoinExec"]


def bound_join_keys(plan, lsch: Schema, rsch: Schema):
    """Bind both sides' join keys and compute the per-pair common type.

    THE single source of key-promotion truth: the shuffle partitioner and
    the join kernel must hash/compare identical physical values, so both
    call this helper (a divergence would send equal keys to different
    partitions and silently drop matches).
    """
    from ..exprs import bind
    lk = [bind(k, lsch) for k in plan.left_keys]
    rk = [bind(k, rsch) for k in plan.right_keys]
    common = [T.common_type(a.dtype, b.dtype) for a, b in zip(lk, rk)]
    return lk, rk, common


def materialize_whole(child: TpuExec, ctx: ExecContext,
                      compact: bool = True):
    """Materialize an operator's whole output as ONE spillable handle
    (compact each batch, concat, register) — shared by join-side
    materialization and broadcast exchanges.  ``compact=False`` keeps
    selection masks (SYNC-FREE): the dense-join build programs fold the
    mask in, so the live-count round trip is paid only if the dense
    path rejects."""
    from ..memory.spill import get_catalog
    catalog = get_catalog(ctx.conf)
    handles = []
    for b in child.execute(ctx):
        c = batch_utils.compact(b) if compact else b
        if compact and c.num_rows == 0:
            continue
        if c.capacity > 0:
            handles.append(catalog.register(c, priority=1))
    if not handles:
        return catalog.register(_empty_batch(child.output_schema),
                                priority=1)
    if len(handles) == 1:
        return handles[0]
    whole = batch_utils.compact(
        batch_utils.concat_batches([h.get() for h in handles]))
    for h in handles:
        h.close()
    return catalog.register(whole, priority=1)


def _canon_how(how: str) -> str:
    return {"left_outer": "left", "right_outer": "right",
            "full_outer": "full", "left_semi": "semi",
            "left_anti": "anti"}.get(how, how)


def encode_key_arrays(arrays, batch: ColumnBatch, key_exprs, dicts: dict):
    """Substitute int32 dictionary codes for string bare-column join keys.

    ``dicts`` maps key INDEX → StringDictionary and is shared across both
    join sides (and their exchanges), so codes are comparable everywhere a
    given key is hashed or compared (ops/strings.py).
    """
    from ..exprs import BoundReference
    from ..ops.strings import StringDictionary
    from .planner import strip_alias
    arrays = list(arrays)
    for ki, e in enumerate(key_exprs):
        core = strip_alias(e)
        if isinstance(core, BoundReference) and core.dtype is not None \
                and core.dtype.is_string:
            col = batch.columns[core.ordinal]
            if isinstance(col, HostStringColumn):
                d = dicts.setdefault(ki, StringDictionary())
                codes, valid = d.encode(col.array)
                arrays[core.ordinal] = (
                    jnp.asarray(codes),
                    jnp.asarray(valid) if valid is not None else None)
    return tuple(arrays)


class SortMergeJoinExec(TpuExec):
    def __init__(self, plan, left: TpuExec, right: TpuExec, conf,
                 string_dicts: Optional[dict] = None):
        super().__init__([left, right])
        self.plan = plan
        self._conf = conf
        self.how = _canon_how(plan.how)
        self.condition = plan.condition
        # single source of truth for join output shape: L.Join.schema()
        self._schema = plan.schema()
        self.using = list(getattr(plan, "using", []) or [])
        self.string_dicts = string_dicts if string_dicts is not None else {}

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def node_desc(self):
        return f"TpuSortMergeJoin [{self.how}]"

    # -- helpers ------------------------------------------------------------------
    def _bound_keys(self) -> Tuple[List[Expression], List[Expression],
                                   List[T.DataType]]:
        return bound_join_keys(self.plan, self.children[0].output_schema,
                               self.children[1].output_schema)

    def _fingerprint(self) -> str:
        lk, rk, ct = self._bound_keys()
        return "|".join([self.how]
                        + [e.fingerprint() for e in lk]
                        + [e.fingerprint() for e in rk]
                        + [str(c) for c in ct])

    def _materialize(self, ctx: ExecContext, side: int):
        """Materialize one side as a spillable handle (LazySpillableColumnar-
        Batch analog): while the other side executes, this one can be
        evicted to host under memory pressure."""
        return materialize_whole(self.children[side], ctx)

    def _inject_smj_filter(self, ctx, lh) -> None:
        """Push the materialized LEFT side's key stats into the RIGHT
        side's scan as runtime predicates.  Legal whenever right rows
        that match no left key are never emitted (inner/left/semi/anti/
        existence) — the exact-range/IN-list version of the reference's
        bloom-filter join runtime filters
        (GpuBloomFilterMightContain.scala)."""
        conf = ctx.conf
        if not conf["spark.rapids.tpu.sql.dpp.enabled"]:
            return
        lk, rk, common = self._bound_keys()
        if len(common) != 1:
            return
        ct = common[0]
        ik = _int_key_caster(ct)
        try:
            kind = np.dtype(ct.numpy_dtype).kind
        except TypeError:
            return
        if kind not in "iu":
            return
        from ..exprs import BoundReference
        from .planner import strip_alias
        core = strip_alias(rk[0])
        if not isinstance(core, BoundReference):
            return
        rname = self.children[1].output_schema.names()[core.ordinal]
        target = _scan_origin(self.children[1], rname)
        if target is None:
            return
        scan, scol = target
        build = lh.get()
        fp = self._fingerprint() + "|smjfilter"

        def build_stats():
            @program("smj_filter_stats")
            def f(b_arrays, n_build):
                b_cap = next(a[0].shape[0] for a in b_arrays
                             if a is not None)
                d, ok = _eval_int_key(lk[0], b_arrays, b_cap, n_build, ct,
                                      ik)
                big = jnp.array(np.iinfo(np.int64).max, dtype=jnp.int64)
                d64 = d.astype(jnp.int64)
                kmin = jnp.min(jnp.where(ok, d64, big))
                kmax = jnp.max(jnp.where(ok, d64, -big))
                n_valid = jnp.sum(ok.astype(jnp.int64))
                s = jnp.sort(jnp.where(ok, d64, big))
                uniq = jnp.concatenate(
                    [jnp.ones((1,), bool), s[1:] != s[:-1]])
                n_distinct = jnp.sum((uniq & (s != big)).astype(jnp.int64))
                return jnp.stack([kmin, kmax, n_valid, n_distinct])
            return f

        b_arrays = _dev_arrays(build)
        b_arrays = encode_key_arrays(b_arrays, build, lk, self.string_dicts)
        fn = _cached_program("smj-filter-stats|" + fp, build_stats)
        kmin, kmax, n_valid, n_distinct = region_scalars(
            fn(b_arrays, np.int32(build.num_rows)))
        max_in = conf["spark.rapids.tpu.sql.dpp.maxInKeys"]
        cap = bucket_capacity(max_in)

        def values_fn():
            def build_vals():
                @program("smj_filter_vals")
                def g(b_arrays, n_build):
                    b_cap = next(a[0].shape[0] for a in b_arrays
                                 if a is not None)
                    d, ok = _eval_int_key(lk[0], b_arrays, b_cap, n_build,
                                          ct, ik)
                    big = jnp.array(np.iinfo(np.int64).max,
                                    dtype=jnp.int64)
                    s = jnp.sort(jnp.where(ok, d.astype(jnp.int64), big))
                    uniq = jnp.concatenate(
                        [jnp.ones((1,), bool), s[1:] != s[:-1]])
                    u = jnp.sort(jnp.where(uniq, s, big))
                    return u[:cap] if u.shape[0] >= cap else u
                return g

            gfn = _cached_program(f"smj-filter-vals|{fp}|{cap}",
                                  build_vals)
            vals = fetch(gfn(b_arrays, np.int32(build.num_rows)))  # fusion-ok (lazy DPP values: demanded by the scan, outside the region's member pulls)
            return vals[vals != np.iinfo(np.int64).max].tolist()

        scan.runtime_predicates = _runtime_key_preds(
            scol, ct, kmin, kmax, n_valid, n_distinct, conf, values_fn)

    # -- execution ----------------------------------------------------------------
    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        m = ctx.metric_set(self.op_id)
        lchild, rchild = self.children
        if lchild.outputs_partitions and rchild.outputs_partitions:
            # AQE-lite (GpuCustomShuffleReaderExec / GpuOverrides
            # re-plan analog): before partitioning anything, stage the
            # smaller-estimated side and read its ACTUAL size — a
            # mis-costed build side under the broadcast threshold flips
            # this shuffled join to a broadcast join at runtime, and the
            # staged handles feed whichever path wins (no wasted work)
            flipped = self._try_runtime_broadcast(ctx, m)
            if flipped is not None:
                yield from flipped
                return
            # shuffled join: equal keys land in the same partition on both
            # sides, so partition pairs join independently (bounded memory)
            lgen, rgen = lchild.execute(ctx), rchild.execute(ctx)
            limit = ctx.conf["spark.rapids.tpu.sql.batchSizeRows"]
            try:
                for lb, rb in zip(lgen, rgen):
                    if lb.num_rows == 0 and rb.num_rows == 0:
                        continue
                    if lb.num_rows + rb.num_rows > limit:
                        yield from self._sub_partition_join(ctx, m, lb, rb)
                        continue
                    yield self._join_pair(ctx, m, lb, rb)
            finally:
                # close BOTH sides deterministically: zip leaves the right
                # generator suspended, and a DCN exchange's cleanup holds a
                # collective barrier that must not wait on garbage
                # collection to run
                lgen.close()
                rgen.close()
            return
        lh = self._materialize(ctx, 0)
        # runtime join filter (GpuBloomFilterMightContain analog, exact
        # instead of probabilistic): once the left side materializes, its
        # key range/IN-list prunes the right side's scan before it reads
        if self.how in ("inner", "left", "semi", "anti", "existence"):
            self._inject_smj_filter(ctx, lh)
        rh = self._materialize(ctx, 1)
        try:
            yield self._join_pair(ctx, m, lh.get(), rh.get())
        finally:
            lh.close()
            rh.close()

    def _try_runtime_broadcast(self, ctx, m):
        """Flip shuffle->broadcast when a staged exchange input is
        actually under the threshold (VERDICT r4 item 7)."""
        conf = ctx.conf
        if not conf["spark.rapids.tpu.sql.aqe.enabled"]:
            return None
        if conf["spark.rapids.tpu.shuffle.mode"] != "CACHE_ONLY":
            return None  # host/ICI transports own their staging
        threshold = conf["spark.rapids.tpu.sql.autoBroadcastJoinThreshold"]
        if threshold < 0 or self.condition is not None:
            return None
        from .exchange_exec import ShuffleExchangeExec
        if not all(isinstance(c, ShuffleExchangeExec)
                   for c in self.children):
            return None
        legal = _legal_build_sides(self.how)
        if not legal:
            return None
        ests = []
        for i in legal:
            b = _estimated_bytes(self.plan.children[i])
            ests.append((i, b if b is not None else float("inf")))
        cand = min(ests, key=lambda t: t[1])[0]
        exch = self.children[cand]
        if not exch.staged_fits(ctx, threshold):
            return None  # staged handles reused by the shuffle path
        m.add("aqeShuffleToBroadcast", 1)
        from ..batch import Schema as _S

        class _StagedExec(TpuExec):
            def __init__(self, schema, handles):
                super().__init__()
                self._schema = schema
                self._handles = handles

            @property
            def output_schema(self):
                return self._schema

            def node_desc(self):
                return "TpuAQEStagedInput"

            def execute(self, _ctx):
                for h in self._handles:
                    yield h.get()

        build = BroadcastExchangeExec(_StagedExec(
            exch.output_schema, exch.stage_input(ctx)))
        probe = self.children[1 - cand].children[0]
        pair = [None, None]
        pair[cand] = build
        pair[1 - cand] = probe
        bj = BroadcastJoinExec(self.plan, pair[0], pair[1], conf, cand,
                               string_dicts=self.string_dicts)

        def run():
            try:
                yield from bj.execute(ctx)
            finally:
                # the staged handles fed the broadcast path; release them
                # (the shuffle path would have closed them itself)
                for h in exch.stage_input(ctx):
                    h.close()
                exch._staged_raw = None

        return run()

    def _sub_partition_join(self, ctx, m, lb: ColumnBatch, rb: ColumnBatch
                            ) -> Iterator[ColumnBatch]:
        """Re-partition an OVERSIZED partition pair (a skewed/huge hash
        bucket) into sub-pairs by a SECOND, independent key hash
        (xxhash64, vs the exchange's murmur3) and join each sub-pair —
        exact for every join type since equal keys still co-locate.
        GpuSubPartitionHashJoin.scala analog; spark.rapids.tpu.sql.join.
        subPartitions controls the fan-out."""
        from ..ops.hashing import xxhash64_columns
        k = max(2, ctx.conf["spark.rapids.tpu.sql.join.subPartitions"])
        m.add("subPartitionedPairs", 1)
        lk, rk, common = self._bound_keys()

        def sub_pid_fn(keys):
            fp = ("join-subpid|" + str(k) + "|"
                  + "|".join(e.fingerprint() for e in keys))

            def build():
                @program("join_subpid")
                def f(arrays, sel, num_rows):
                    cap = next(a[0].shape[0] for a in arrays
                               if a is not None)
                    active = jnp.arange(cap, dtype=jnp.int32) < num_rows
                    if sel is not None:
                        active = active & sel
                    ectx = EvalContext(list(arrays), cap, active=active)
                    kvs = [e.eval(ectx) for e in keys]
                    kvs = [(d, v) if ct.is_string
                           else (promote_physical(d, e.dtype, ct), v)
                           for (d, v), e, ct in zip(kvs, keys, common)]
                    h = xxhash64_columns(kvs)
                    pid = (h % jnp.int64(k)).astype(jnp.int32)
                    pid = jnp.where(pid < 0, pid + k, pid)
                    return jnp.where(active, pid, k)
                return f

            return _cached_program(fp, build)

        def split(batch, keys):
            arrays = _dev_arrays(batch)
            arrays = encode_key_arrays(arrays, batch, keys,
                                       self.string_dicts)
            pids = sub_pid_fn(keys)(arrays, batch.sel,
                                    np.int32(batch.num_rows))
            outs = []
            for p in range(k):
                sel = pids == p
                outs.append(batch_utils.compact(ColumnBatch(
                    batch.schema, batch.columns, batch.num_rows, sel)))
            return outs

        l_parts = split(lb, lk)
        r_parts = split(rb, rk)
        for lp, rp in zip(l_parts, r_parts):
            if lp.num_rows == 0 and rp.num_rows == 0:
                continue
            yield self._join_pair(ctx, m, lp, rp)

    def _join_pair(self, ctx, m, left: ColumnBatch,
                   right: ColumnBatch) -> ColumnBatch:
        with counted_span("join_exec_s", self.op_id, "join:pair", "join"):
            return self._join_pair_impl(m, left, right)

    def _count_semi_anti(self) -> None:
        if self.how in ("semi", "anti", "existence"):
            QueryStats.get().join_semi_anti += 1

    def _join_pair_impl(self, m, left: ColumnBatch,
                        right: ColumnBatch) -> ColumnBatch:
        self._count_semi_anti()
        if self.condition is not None and self.how in ("left", "semi",
                                                       "anti",
                                                       "existence",
                                                       "right", "full"):
            with m.time("opTime"):
                out = self._conditioned_probe_join(left, right)
            if out.sel is None:
                m.add("numOutputRows", out.num_rows)
            else:
                m.add_deferred("numOutputRows", jnp.sum(out.active_mask()))
            return out
        with m.time("opTime"):
            out = self._join(left, right)
        if self.condition is not None:
            out = self._apply_residual(out)
        # row_count semantics (not num_rows): the residual/semi/anti
        # selection mask must be reflected in the metric — but deferred,
        # never as a per-pair blocking fetch
        if out.sel is None:
            m.add("numOutputRows", out.num_rows)
        else:
            m.add_deferred("numOutputRows", jnp.sum(out.active_mask()))
        return out

    def _conditioned_probe_join(self, left: ColumnBatch,
                                right: ColumnBatch) -> ColumnBatch:
        """Residual conditions participate in MATCHING (GpuHashJoin.scala
        conditional joins, all join types — GpuHashJoin.scala:104-383),
        not post-filtering.  Shape: inner candidate expansion → evaluate
        the condition on the pairs → per-probe (and, for right/full,
        per-build) surviving-match counts → semi/anti select probe rows;
        left/full null-pad probes with zero surviving matches; right/full
        null-pad build rows with zero surviving matches."""
        from ..exprs import bind
        how = self.how
        lo, matches, b_perm = self._match_state(left, right, probe_side=0)
        p_cap, b_cap = left.capacity, right.capacity
        active = jnp.arange(p_cap, dtype=jnp.int32) < left.num_rows
        if left.sel is not None:
            active = active & left.sel
        counts = jnp.where(active, matches, 0)
        offsets = jnp.cumsum(counts)
        # one host sync: candidate-pair count (batched with any staged
        # region stats when a fused region is active)
        total = region_scalars(offsets[-1])[0]
        out_cap = bucket_capacity(max(total, 1))
        _count_expansion(total, out_cap)

        fp = self._fingerprint() + "|condexpand"

        def build_fn():
            @program("join_cond_expand")
            def f(offsets, counts, lo, matches, b_perm, out_cap_arr):
                return expand_pairs(offsets, counts, lo, matches, b_perm,
                                    out_cap_arr.shape[0])
            return f

        fn = _cached_program("join-condexpand|" + fp, build_fn)
        pi, bi, in_range = fn(offsets, counts, lo, matches, b_perm,
                              jnp.zeros((out_cap,), dtype=jnp.int8))

        # pair columns in (left ++ right) order for condition binding
        combined = Schema(list(left.schema.fields)
                          + list(right.schema.fields))
        p_cols = _gather_cols(left, jnp.where(in_range, pi, -1),
                              valid_if="neg_is_null")
        b_cols = _gather_cols(right, bi, valid_if="neg_is_null")
        pair = ColumnBatch(combined, p_cols["cols"] + b_cols["cols"],
                           out_cap, in_range)
        cond = bind(self.condition, combined)

        def build_cond():
            @program("join_cond")
            def g(arrays, sel, pi, bi, p_cap_arr, b_cap_arr):
                cap = next(a[0].shape[0] for a in arrays if a is not None)
                act = sel
                ectx = EvalContext(list(arrays), cap, active=act)
                d, v = cond.eval(ectx)
                keep = d if v is None else (d & v)
                keep = keep & act
                surviving = jax.ops.segment_sum(
                    keep.astype(jnp.int32), pi,
                    num_segments=p_cap_arr.shape[0])
                b_surviving = jax.ops.segment_sum(
                    keep.astype(jnp.int32),
                    jnp.clip(bi, 0, b_cap_arr.shape[0] - 1),
                    num_segments=b_cap_arr.shape[0])
                return keep, surviving, b_surviving
            return g

        gfn = _cached_program(
            "join-cond|" + fp + "|" + cond.fingerprint(), build_cond)
        arrays = tuple((c.data, c.valid) if isinstance(c, DeviceColumn)
                       else None for c in pair.columns)
        keep, surviving, b_surviving = gfn(
            arrays, in_range, pi, bi,
            jnp.zeros((p_cap,), dtype=jnp.int8),
            jnp.zeros((b_cap,), dtype=jnp.int8))

        if how in ("semi", "anti"):
            sel = (surviving > 0) if how == "semi" else (surviving == 0)
            return ColumnBatch(self._schema, left.columns, left.num_rows,
                               sel & active)
        if how == "existence":
            exists = DeviceColumn(T.BOOLEAN, surviving > 0, None)
            return ColumnBatch(self._schema,
                               list(left.columns) + [exists],
                               left.num_rows, left.sel)
        # outer joins: surviving pairs + null-padded unmatched rows on
        # each preserved side
        matched_out = ColumnBatch(self._schema, pair.columns, out_cap, keep)
        from ..batch import logical_to_arrow

        def _null_cols(schema, cap_):
            cols: List = []
            for f in schema:
                if f.dtype.is_host_carried:
                    import pyarrow as pa
                    cols.append(HostStringColumn(
                        pa.nulls(cap_, type=logical_to_arrow(f.dtype))))
                else:
                    shape = (cap_, 2) if getattr(
                        f.dtype, "is_wide_decimal", False) else (cap_,)
                    cols.append(DeviceColumn(
                        f.dtype,
                        jnp.zeros(shape, dtype=f.dtype.numpy_dtype),
                        jnp.zeros((cap_,), dtype=bool)))
            return cols

        parts = [matched_out]
        if how in ("left", "full"):
            pad_cols = list(left.columns) + _null_cols(right.schema, p_cap)
            parts.append(ColumnBatch(self._schema, pad_cols,
                                     left.num_rows,
                                     active & (surviving == 0)))
        if how in ("right", "full"):
            b_active = jnp.arange(b_cap, dtype=jnp.int32) < right.num_rows
            if right.sel is not None:
                b_active = b_active & right.sel
            pad_cols = _null_cols(left.schema, b_cap) + list(right.columns)
            parts.append(ColumnBatch(self._schema, pad_cols,
                                     right.num_rows,
                                     b_active & (b_surviving == 0)))
        if len(parts) == 1:
            return matched_out
        return batch_utils.concat_batches(parts)

    def _apply_residual(self, batch: ColumnBatch) -> ColumnBatch:
        """Inner-join residual condition as a post-selection (non-equi part).
        The planner only routes inner joins with conditions here."""
        from ..exprs import bind
        cond = bind(self.condition, batch.schema)

        def build():
            @program("join_residual")
            def f(arrays, sel, num_rows):
                cap = next(a[0].shape[0] for a in arrays if a is not None)
                active = jnp.arange(cap, dtype=jnp.int32) < num_rows
                if sel is not None:
                    active = active & sel
                ectx = EvalContext(list(arrays), cap, active=active)
                d, v = cond.eval(ectx)
                keep = d if v is None else (d & v)
                return active & keep
            return f

        fn = _cached_program("join-residual|" + cond.fingerprint(), build)
        arrays = tuple((c.data, c.valid) if isinstance(c, DeviceColumn)
                       else None for c in batch.columns)
        sel = fn(arrays, batch.sel, jnp.int32(batch.num_rows))
        return ColumnBatch(batch.schema, batch.columns, batch.num_rows, sel)

    # -- the join kernel ----------------------------------------------------------
    def _join(self, left: ColumnBatch, right: ColumnBatch) -> ColumnBatch:
        how = self.how
        if how == "cross":
            return self._cross(left, right)
        if how == "right":
            # right join = mirrored left join with output columns re-split
            return self._outer_join(left, right, probe_side=1)
        if how in ("inner", "left", "full"):
            return self._outer_join(left, right, probe_side=0)
        if how in ("semi", "anti"):
            return self._semi_anti(left, right)
        if how == "existence":
            return self._existence(left, right)
        raise NotImplementedError(f"join type {how}")

    def _existence(self, left: ColumnBatch,
                   right: ColumnBatch) -> ColumnBatch:
        """ExistenceJoin (GpuHashJoin.scala ExistenceJoin handling): every
        left row survives, plus a boolean column marking key matches."""
        _, matches, _ = self._match_state(left, right, probe_side=0)
        exists = DeviceColumn(T.BOOLEAN, matches > 0, None)
        return ColumnBatch(self._schema, list(left.columns) + [exists],
                           left.num_rows, left.sel)

    def _match_state(self, probe: ColumnBatch, build: ColumnBatch,
                     probe_side: int):
        """Compute (lo, hi, matches, build_perm) device arrays."""
        lk, rk, common = self._bound_keys()
        pk, bk = (lk, rk) if probe_side == 0 else (rk, lk)
        fp = self._fingerprint() + f"|ps{probe_side}"

        def build_fn():
            @program("join_match")
            def f(p_arrays, b_arrays, n_probe, n_build):
                p_cap = next(a[0].shape[0] for a in p_arrays if a is not None)
                b_cap = next(a[0].shape[0] for a in b_arrays if a is not None)
                p_active = jnp.arange(p_cap, dtype=jnp.int32) < n_probe
                b_active = jnp.arange(b_cap, dtype=jnp.int32) < n_build
                pctx = EvalContext(list(p_arrays), p_cap, active=p_active)
                bctx = EvalContext(list(b_arrays), b_cap, active=b_active)
                pkv = [e.eval(pctx) for e in pk]
                bkv = [e.eval(bctx) for e in bk]
                # promote to common key types, then union-encode (string
                # keys arrive as int32 dictionary codes — no promotion)
                pkv = [(d, v) if ct.is_string
                       else (promote_physical(d, e.dtype, ct), v)
                       for (d, v), e, ct in zip(pkv, pk, common)]
                bkv = [(d, v) if ct.is_string
                       else (promote_physical(d, e.dtype, ct), v)
                       for (d, v), e, ct in zip(bkv, bk, common)]
                return match_ranges(pkv, bkv, rows_ok(pkv, p_active),
                                    rows_ok(bkv, b_active))
            return f

        fn = _cached_program("join-match|" + fp, build_fn)
        p_arrays = _dev_arrays(probe)
        b_arrays = _dev_arrays(build)
        p_arrays = encode_key_arrays(p_arrays, probe, pk, self.string_dicts)
        b_arrays = encode_key_arrays(b_arrays, build, bk, self.string_dicts)
        return fn(p_arrays, b_arrays, np.int32(probe.num_rows),
                  np.int32(build.num_rows))

    def _semi_anti(self, left: ColumnBatch, right: ColumnBatch) -> ColumnBatch:
        lo, matches, b_perm = self._match_state(left, right, probe_side=0)
        active = jnp.arange(left.capacity, dtype=jnp.int32) < left.num_rows
        sel = (matches > 0) if self.how == "semi" else (matches == 0)
        sel = sel & active
        return ColumnBatch(self._schema, left.columns, left.num_rows, sel)

    def _outer_join(self, left: ColumnBatch, right: ColumnBatch,
                    probe_side: int) -> ColumnBatch:
        how = self.how
        probe, build = (left, right) if probe_side == 0 else (right, left)
        lo, matches, b_perm = self._match_state(probe, build, probe_side)
        outer = how in ("left", "full", "right")
        counts = jnp.maximum(matches, 1) if outer else matches
        active = jnp.arange(probe.capacity, dtype=jnp.int32) < probe.num_rows
        counts = jnp.where(active, counts, 0)
        offsets = jnp.cumsum(counts)
        extra = 0
        b_unmatched = None
        if how == "full":
            # build-side rows with no probe match are appended afterwards;
            # output size + unmatched count ride ONE sync together
            b_unmatched = self._unmatched_build_mask(probe, build, lo, matches,
                                                     b_perm)
            total, extra = region_scalars(
                (offsets[-1], jnp.sum(b_unmatched)))
        else:
            # the one host sync (output size; region-batched when fused)
            total = region_scalars(offsets[-1])[0]
        out_cap = bucket_capacity(max(total + extra, 1))
        _count_expansion(total, out_cap)

        fp = self._fingerprint() + f"|expand{probe_side}"

        def build_fn():
            @program("join_expand")
            def f(offsets, counts, lo, matches, b_perm, out_cap_arr):
                pi, bi, _matched = expand_pairs(
                    offsets, counts, lo, matches, b_perm,
                    out_cap_arr.shape[0])
                return pi, bi
            return f

        fn = _cached_program("join-expand|" + fp, build_fn)
        pi, bi = fn(offsets, counts, lo, matches, b_perm,
                    jnp.zeros((out_cap,), dtype=jnp.int8))

        probe_null_ok = how in ("full",)  # probe side can be null-padded
        p_cols = _gather_cols(probe, pi, valid_if=None)
        b_cols = _gather_cols(build, bi, valid_if="neg_is_null")
        if how == "full" and extra > 0:
            p_cols, b_cols = self._append_unmatched_build(
                probe, build, b_unmatched, p_cols, b_cols, total, out_cap)
            total += extra
        return self._assemble(probe, build, p_cols, b_cols, probe_side, total,
                              out_cap)

    def _unmatched_build_mask(self, probe, build, lo, matches, b_perm):
        """Build rows matched by no probe row (for FULL outer)."""
        fp = self._fingerprint() + "|unmatched"

        def build_fn():
            @program("join_unmatched")
            def f(lo, matches, b_perm, n_build):
                b_active = (jnp.arange(b_perm.shape[0], dtype=jnp.int32)
                            < n_build)
                return unmatched_build(lo, matches, b_perm, b_active)
            return f

        fn = _cached_program("join-unmatched|" + fp, build_fn)
        return fn(lo, matches, b_perm, jnp.int32(build.num_rows))

    def _append_unmatched_build(self, probe, build, b_unmatched, p_cols,
                                b_cols, total, out_cap):
        """FULL outer: place unmatched build rows after the expansion rows."""
        # destination slots total..total+extra-1 (host-side index math; the
        # unmatched count is already synced)
        # ONE batched fetch for the mask and both index arrays
        un_mask, pi_full, bi_full = fetch(  # fusion-ok (full-row index arrays, data-dependent size: not a stats vector the prologue can pre-stage)
            (b_unmatched, p_cols["idx"], b_cols["idx"]))
        un_idx = np.flatnonzero(un_mask)
        dest = np.arange(total, total + len(un_idx))
        pi_full = np.array(pi_full)
        bi_full = np.array(bi_full)
        pi_full[dest] = -1
        bi_full[dest] = un_idx
        p_cols = _gather_cols(probe, jnp.asarray(pi_full),
                              valid_if="neg_is_null")
        b_cols = _gather_cols(build, jnp.asarray(bi_full),
                              valid_if="neg_is_null")
        return p_cols, b_cols

    def _assemble(self, probe, build, p_cols, b_cols, probe_side, total,
                  out_cap) -> ColumnBatch:
        using = set(self.using)
        if probe_side == 0:
            lcols, lsch = p_cols, probe.schema
            rcols, rsch = b_cols, build.schema
        else:
            lcols, lsch = b_cols, build.schema
            rcols, rsch = p_cols, probe.schema
        cols: List = []
        for f, c in zip(lsch, lcols["cols"]):
            # using-join key columns are coalesced across sides so unmatched
            # right/full rows still show the key (Spark USING semantics)
            if f.name in using and self.how in ("right", "full") \
                    and f.name in rsch:
                rc = rcols["cols"][rsch.index_of(f.name)]
                if isinstance(c, DeviceColumn) and isinstance(rc, DeviceColumn):
                    lv = c.valid if c.valid is not None else \
                        jnp.ones_like(c.data, dtype=bool)
                    data = jnp.where(lv, c.data, rc.data)
                    # coalesce: null only where BOTH sides are null
                    valid = None if rc.valid is None else (lv | rc.valid)
                    c = DeviceColumn(f.dtype, data, valid)
                elif isinstance(c, HostStringColumn) \
                        and isinstance(rc, HostStringColumn):
                    import pyarrow.compute as pc
                    c = HostStringColumn(pc.coalesce(c.array, rc.array))
            cols.append(c)
        for f, c in zip(rsch, rcols["cols"]):
            if f.name in using:
                continue
            cols.append(c)
        return ColumnBatch(self._schema, cols, total)

    def _cross(self, left: ColumnBatch, right: ColumnBatch) -> ColumnBatch:
        n_l, n_r = left.num_rows, right.num_rows
        total = n_l * n_r
        out_cap = bucket_capacity(max(total, 1))
        _count_expansion(total, out_cap)
        j = jnp.arange(out_cap, dtype=jnp.int32)
        pi = jnp.where(j < total, j // max(n_r, 1), -1)
        bi = jnp.where(j < total, j % max(n_r, 1), -1)
        p_cols = _gather_cols(left, pi, valid_if="neg_is_null")
        b_cols = _gather_cols(right, bi, valid_if="neg_is_null")
        return self._assemble(left, right, p_cols, b_cols, 0, total, out_cap)


# ---------------------------------------------------------------------------------
# Broadcast joins
# ---------------------------------------------------------------------------------

class BroadcastExchangeExec(TpuExec):
    """Materialize the (small) build side ONCE as a single spillable batch.

    Reference: GpuBroadcastExchangeExec.scala:352 — the build side is
    collected and shared by every task.  In-process that means one
    materialized batch; over DCN every rank all-gathers it
    (parallel/dcn.py); under ICI SPMD it feeds the mesh replicated
    (parallel/spmd.py P() in_spec)."""

    outputs_broadcast = True

    def __init__(self, child: TpuExec):
        super().__init__([child])

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def node_desc(self):
        return "TpuBroadcastExchange"

    def materialize(self, ctx: ExecContext, compact: bool = True):
        """One spillable handle holding the whole child output.
        ``compact=False`` (the dense-join path) defers the live-count
        sync until/unless the dense build rejects.

        With the cross-query cache's broadcast tier enabled, the
        materialized build is shared across queries via a refcounted
        :class:`..cache.CachedBuildHandle` — a hit skips the whole
        build (decode, upload, concat) and, because cached entries
        carry their probed dense-key stats, the dense join's blocking
        stats fetches too."""
        m = ctx.metric_set(self.op_id)
        from ..cache import cache_enabled
        if cache_enabled(ctx.conf, "broadcast"):
            from ..cache import broadcast_key, get_query_cache
            key = broadcast_key(self.children[0], compact, ctx.device)
            if key is not None:
                qcache = get_query_cache(ctx.conf)
                hit = qcache.lookup_broadcast(key, op_id=self.op_id)
                if hit is not None:
                    m.add("cacheHitBuilds", 1)
                    return hit
                with m.time("buildTime"):
                    h = materialize_whole(self.children[0], ctx,
                                          compact=compact)
                return qcache.insert_broadcast(key, h, op_id=self.op_id)
        with m.time("buildTime"):
            return materialize_whole(self.children[0], ctx,
                                     compact=compact)

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        h = self.materialize(ctx)
        try:
            yield h.get()
        finally:
            h.close()


class BroadcastJoinExec(SortMergeJoinExec):
    """Join a streamed probe side against a broadcast build side.

    Reference: GpuBroadcastHashJoinExecBase.scala (equi, gather-map per
    probe batch), GpuBroadcastNestedLoopJoinExecBase.scala (cross).  The
    probe side streams batch-by-batch — the big (fact) side never
    materializes wholesale and is never shuffled; each probe batch joins
    the resident build batch independently.  ``build_side`` must be the
    kernel's natural build for the join type (right, except left for
    how=right): the planner guarantees it (plan_broadcast_join)."""

    # probe side streams: the region planner may chain through it.  The
    # build side (BroadcastExchangeExec) is a region boundary.
    region_fusible = True

    def __init__(self, plan, left: TpuExec, right: TpuExec, conf,
                 build_side: int, string_dicts: Optional[dict] = None):
        super().__init__(plan, left, right, conf, string_dicts=string_dicts)
        self.build_side = build_side
        assert build_side in _legal_build_sides(self.how), \
            f"cannot broadcast side {build_side} of a {self.how} join"
        assert isinstance(self.children[build_side], BroadcastExchangeExec)

    def _join(self, left: ColumnBatch, right: ColumnBatch) -> ColumnBatch:
        if self.how == "inner" and self.build_side == 0:
            # inner join is symmetric: probe the (streamed) right side so
            # the broadcast left side is the build
            return self._outer_join(left, right, probe_side=1)
        return super()._join(left, right)

    def _match_state(self, probe: ColumnBatch, build: ColumnBatch,
                     probe_side: int):
        """Broadcast fast path for single equi-keys: sort the (small)
        resident build side ONCE, then each probe batch is two
        ``searchsorted`` calls — no per-batch union concat + lexsort over
        probe+build (the generic kernel's per-batch cost, which dominates
        dim-fact joins).  Multi-key joins fall back to the union kernel."""
        lk, rk, common = self._bound_keys()
        if len(common) != 1:
            return super()._match_state(probe, build, probe_side)
        pk, bk = (lk, rk) if probe_side == 0 else (rk, lk)
        ct = common[0]
        np_dt = np.dtype(np.int32) if ct.is_string \
            else np.dtype(ct.numpy_dtype)
        floating = np.issubdtype(np_dt, np.floating)
        if floating:
            # floats ride as total-order int bit patterns (sign-magnitude
            # flip) with -0.0 normalized to +0.0 and NaN canonicalized to
            # the all-ones image (signed -1), reachable by no non-NaN
            # float — Spark's NaN==NaN join semantics via ordinary
            # integer searchsorted
            ik = np.dtype(np.int32) if np_dt.itemsize == 4 \
                else np.dtype(np.int64)
            sentinel = np.array(np.iinfo(ik).max, dtype=ik)
        elif np.issubdtype(np_dt, np.integer):
            ik = None
            sentinel = np.array(np.iinfo(np_dt).max, dtype=np_dt)
        else:  # bool / object-carried keys: keep the generic kernel
            return super()._match_state(probe, build, probe_side)

        csr = self._csr_match_state(probe, build, probe_side, pk, bk,
                                    ct)
        if csr is not None:
            return csr

        def orderable(d):
            # `sentinel` (the int max) is reachable by no key image: it
            # would require a -0.0 bit pattern, which _float_orderable
            # normalizes away — so the invalid-tail sentinel stays unique
            return _float_orderable(d, ik) if floating else d
        fp = self._fingerprint() + f"|bfast{probe_side}"

        def build_sort():
            @program("bjoin_sort")
            def f(b_arrays, n_build):
                b_cap = next(a[0].shape[0] for a in b_arrays
                             if a is not None)
                b_active = jnp.arange(b_cap, dtype=jnp.int32) < n_build
                bctx = EvalContext(list(b_arrays), b_cap, active=b_active)
                d, v = bk[0].eval(bctx)
                if not ct.is_string:
                    d = promote_physical(d, bk[0].dtype, ct)
                d = orderable(d)
                ok = b_active if v is None else (b_active & v)
                n_valid = jnp.sum(ok.astype(jnp.int32))
                # sort valid rows first (by flag, then key), then OVERWRITE
                # the invalid tail with the sentinel so the array is
                # globally sorted — a value sentinel alone would collide
                # with legitimate keys equal to the dtype's max
                perm = jnp.lexsort((d, ~ok))
                d_sorted = jnp.where(
                    jnp.arange(b_cap, dtype=jnp.int32) < n_valid,
                    d[perm], sentinel)
                return d_sorted, perm.astype(jnp.int32), n_valid
            return f

        cache = getattr(self, "_bfast_cache", None)
        # the build batch itself rides in the cache tuple so its id cannot
        # be recycled by CPython for a different batch while cached
        if cache is None or cache[0] != (probe_side, id(build)):
            fn = _cached_program("bjoin-sort|" + fp, build_sort)
            b_arrays = _dev_arrays(build)
            b_arrays = encode_key_arrays(b_arrays, build, bk,
                                         self.string_dicts)
            sorted_keys, b_perm, n_valid = fn(b_arrays,
                                              np.int32(build.num_rows))
            cache = ((probe_side, id(build)), build, sorted_keys, b_perm,
                     n_valid)
            self._bfast_cache = cache
        _, _, sorted_keys, b_perm, n_valid = cache

        def build_probe():
            @program("bjoin_probe")
            def g(p_arrays, sorted_keys, n_valid, n_probe):
                p_cap = next(a[0].shape[0] for a in p_arrays
                             if a is not None)
                p_active = jnp.arange(p_cap, dtype=jnp.int32) < n_probe
                pctx = EvalContext(list(p_arrays), p_cap, active=p_active)
                d, v = pk[0].eval(pctx)
                if not ct.is_string:
                    d = promote_physical(d, pk[0].dtype, ct)
                d = orderable(d)
                p_ok = p_active if v is None else (p_active & v)
                lo = jnp.searchsorted(sorted_keys, d, side="left")
                hi = jnp.searchsorted(sorted_keys, d, side="right")
                lo = jnp.minimum(lo, n_valid).astype(jnp.int32)
                hi = jnp.minimum(hi, n_valid).astype(jnp.int32)
                matches = jnp.where(p_ok, hi - lo, 0)
                return lo, matches
            return g

        gfn = _cached_program("bjoin-probe|" + fp, build_probe)
        p_arrays = _dev_arrays(probe)
        p_arrays = encode_key_arrays(p_arrays, probe, pk, self.string_dicts)
        lo, matches = gfn(p_arrays, sorted_keys, n_valid,
                          np.int32(probe.num_rows))
        return lo, matches, b_perm

    def _csr_match_state(self, probe, build, probe_side, pk, bk, ct):
        """Dense CSR matching for DUPLICATE-keyed builds: counts/starts
        direct-address tables + one stable build sort, so every probe
        batch is TWO gathers — no per-batch sort, no searchsorted (the
        gather wall).  Produces the same (lo, matches, b_perm) contract
        as the sorted path; requires the dense-stats prefetch (bounded
        int domain) to have run.  cuDF-hash-table analog for the
        multi-row-per-key case (GpuHashJoin.scala gather maps)."""
        tagged = getattr(self, "_dense_stats_host", None)
        conf = getattr(self, "_conf", None)
        if tagged is None or conf is None:
            return None
        st_id, st_side, stats = tagged
        # the stats MUST describe this build batch on this side — never
        # trust distant gating for table sizing (silent-corruption trap)
        if st_id != id(build) or st_side != (1 - probe_side):
            return None
        ik = _int_key_caster(ct)
        if ik is None:
            return None
        kmin, kmax, n_valid, _dup = [int(x) for x in stats[:4]]
        if n_valid == 0:
            return None
        domain = kmax - kmin + 1
        if domain <= 0 \
                or domain > conf["spark.rapids.tpu.join.denseDomainCap"]:
            return None
        D = bucket_capacity(domain)
        fp = self._fingerprint() + f"|csr{probe_side}|{D}"

        def build_csr():
            @program("bjoin_csr")
            def f(b_arrays, sel, kmin_s, n_build):
                b_cap = next(a[0].shape[0] for a in b_arrays
                             if a is not None)
                idx_raw, ok, _ = _dense_key_slot(
                    bk[0], b_arrays, b_cap, n_build, ct, ik, kmin_s, D,
                    sel)
                idx = jnp.where(ok, idx_raw, jnp.int64(D))
                counts = jnp.zeros((D,), jnp.int32).at[idx].add(
                    1, mode="drop")
                starts = jnp.concatenate(
                    [jnp.zeros((1,), jnp.int32),
                     jnp.cumsum(counts)[:-1].astype(jnp.int32)])
                # stable grouping of build rows by key slot (one-time)
                perm = jnp.lexsort(
                    (jnp.arange(b_cap, dtype=jnp.int32), idx))
                return counts, starts, perm.astype(jnp.int32)
            return f

        cache = getattr(self, "_csr_cache", None)
        if cache is None or cache[0] != (probe_side, id(build)):
            fn = _cached_program("bjoin-csr|" + fp, build_csr)
            b_arrays = _dev_arrays(build)
            b_arrays = encode_key_arrays(b_arrays, build, bk,
                                         self.string_dicts)
            counts, starts, b_perm = fn(b_arrays, build.sel,
                                        jnp.int64(kmin),
                                        np.int32(build.num_rows))
            cache = ((probe_side, id(build)), build, counts, starts,
                     b_perm)
            self._csr_cache = cache
        _, _, counts, starts, b_perm = cache

        def build_probe():
            @program("bjoin_csr_probe")
            def g(p_arrays, counts, starts, kmin_s, n_probe):
                p_cap = next(a[0].shape[0] for a in p_arrays
                             if a is not None)
                idx, _ok, in_dom = _dense_key_slot(
                    pk[0], p_arrays, p_cap, n_probe, ct, ik, kmin_s, D)
                safe = jnp.clip(idx, 0, D - 1).astype(jnp.int32)
                matches = jnp.where(in_dom, counts[safe], 0)
                lo = jnp.where(in_dom, starts[safe], 0)
                return lo, matches
            return g

        gfn = _cached_program("bjoin-csrprobe|" + fp, build_probe)
        p_arrays = _dev_arrays(probe)
        p_arrays = encode_key_arrays(p_arrays, probe, pk,
                                     self.string_dicts)
        lo, matches = gfn(p_arrays, counts, starts, jnp.int64(kmin),
                          np.int32(probe.num_rows))
        return lo, matches, b_perm

    def node_desc(self):
        side = "left" if self.build_side == 0 else "right"
        kind = "NestedLoop" if self.how == "cross" else "Hash"
        return f"TpuBroadcast{kind}Join [{self.how}] build={side}"

    # -- dense direct-address fast path -------------------------------------------
    #
    # The TPU-native answer to cuDF's device hash table
    # (GpuHashJoin.scala:104 gather maps): when the single equi-key's
    # domain (max-min+1) is bounded and build keys are unique — the
    # dim-fact shape joins live on — build a dense int32 table mapping
    # (key - kmin) -> build row id once, then every probe batch is ONE
    # HBM gather + fused payload gathers in a single dispatch with ZERO
    # host syncs: probe columns pass through untouched under a selection
    # mask (inner/semi/anti) or stay fully live with null-extended build
    # columns (left).  Measured on-chip: a 4M-probe searchsorted pass is
    # ~700 ms while a 4M int32 gather is ~20 ms — this path replaces
    # ~2 searchsorted passes + per-column expansion gathers with ~1+C
    # gathers.

    def _dense_static_ok(self, conf=None) -> bool:
        how = self.how
        if conf is not None:
            # tiny probes: the dense table's build-stats fetch costs a
            # full host round trip that a small probe never earns back;
            # this gate also skips DPP (a tiny probe reads few row
            # groups to begin with) — denseMinProbeRows tunes it
            est = getattr(self, "probe_est_rows", None)
            min_probe = conf["spark.rapids.tpu.join.denseMinProbeRows"]
            if est is not None and min_probe and est < min_probe:
                return False
        if how == "inner":
            pass  # either build side; a residual condition post-filters
        elif how in ("left", "semi", "anti", "existence"):
            if self.build_side != 1 or self.condition is not None:
                return False
        else:
            return False
        lk, rk, common = self._bound_keys()
        if len(common) != 1:
            return False
        return _int_key_caster(common[0]) is not None

    def _dense_payload_fields(self, build: ColumnBatch):
        """Field-index list into build.schema, or None when a needed
        payload column has no dense representation.  STRING payload
        columns ride as dictionary codes: the build side factorizes once
        (it is small), the probe program gathers int32 codes like any
        device column, and assembly decodes back to a plain string
        column — without this, one string dimension attribute (n_name,
        c_name, p_brand...) forces the whole join onto the searchsorted
        kernel."""
        if self.how in ("semi", "anti", "existence"):
            return []
        using = set(self.using)
        if self.build_side == 1:
            idxs = [i for i, f in enumerate(build.schema)
                    if f.name not in using]
        else:
            idxs = list(range(len(build.schema.fields)))
        for i in idxs:
            c = build.columns[i]
            if isinstance(c, DeviceColumn):
                continue
            if isinstance(c, HostStringColumn) \
                    and build.schema.fields[i].dtype.is_string:
                # string payloads of ANY size ride as dictionary codes:
                # the probe output carries a DictStringColumn (codes on
                # device, decode deferred to the consumer), so the old
                # probe-length fetch+decode that capped this at 4096
                # build rows is gone
                continue
            return None  # nested / other host-carried
        return idxs

    def _dense_prefetch(self, build: ColumnBatch, conf) -> None:
        """Dispatch the build-key stats program and start its async
        device→host copy.  Called right after the build materializes, so
        the round trip overlaps the probe side's host work (parquet
        decode, upstream dispatches) instead of blocking the first probe
        batch."""
        cache = getattr(self, "_dense_cache", None)
        if cache is not None and cache[0] == id(build):
            return
        pending = getattr(self, "_dense_pending", None)
        if pending is not None:
            if pending[0] == id(build):
                return
            self._dense_pending = None  # stale build: recompute
        if not conf["spark.rapids.tpu.join.denseDomainCap"]:
            return
        lk, rk, common = self._bound_keys()
        bk = rk if self.build_side == 1 else lk
        ct = common[0]
        ik = _int_key_caster(ct)
        if ik is None:
            return
        fp = self._fingerprint() + f"|dense|bs{self.build_side}"

        # the capped sorted-unique prefix rides in the SAME program and
        # async copy: DPP's IN-list push needs exactly these values, and a
        # separate values program cost a second full round trip per join
        # +1: a truncated-at-exactly-max_in prefix must be DISTINGUISHABLE
        # from a complete distinct set of size max_in
        vcap = bucket_capacity(
            conf["spark.rapids.tpu.sql.dpp.maxInKeys"] + 1)

        # broadcast-reuse fast path: a cached build carries the probed
        # stats from the query that first ran this join shape — the
        # stats program is not even dispatched, and the later
        # _pending_host resolution finds the host copy already present
        # (zero blocking fetches on the hit path)
        skey = ("dense-stats", fp, vcap)
        self._dense_stats_key = skey
        # query-scoped dedupe: a second join node INSTANCE with the same
        # stats program identity over the same materialized build (the
        # same dim table joined twice in one query) shares the first
        # instance's dispatched stats array AND its resolved host copy —
        # the shared pending list means the sync is paid at most once
        # per (program, build) per query, not once per join node
        ctx = getattr(self, "_exec_ctx", None)
        memo = getattr(ctx, "stats_memo", None)
        mkey = (skey, id(build))
        if memo is not None:
            shared = memo.get(mkey)
            if shared is not None:
                self._dense_pending = shared
                return
        ent = getattr(self, "_cache_entry", None)
        if ent is not None:
            host = ent.get_stat(skey)
            if host is not None:
                b_arrays = encode_key_arrays(_dev_arrays(build), build,
                                             bk, self.string_dicts)
                self._dense_pending = [id(build), build, None, b_arrays,
                                       host]
                if memo is not None:
                    memo[mkey] = self._dense_pending
                return

        def build_stats():
            @program("bjoin_dense_stats")
            def f(b_arrays, sel, n_build):
                b_cap = next(a[0].shape[0] for a in b_arrays
                             if a is not None)
                active = jnp.arange(b_cap, dtype=jnp.int32) < n_build
                if sel is not None:
                    active = active & sel
                d, ok = _eval_int_key(bk[0], b_arrays, b_cap, n_build,
                                      ct, ik, active=active)
                big = jnp.array(np.iinfo(np.int64).max, dtype=jnp.int64)
                d64 = d.astype(jnp.int64)
                kmin = jnp.min(jnp.where(ok, d64, big))
                kmax = jnp.max(jnp.where(ok, d64, -big))
                n_valid = jnp.sum(ok.astype(jnp.int64))
                s = jnp.sort(jnp.where(ok, d64, big))
                dup = jnp.sum(((s[1:] == s[:-1]) & (s[1:] != big))
                              .astype(jnp.int64))
                uniq = jnp.concatenate(
                    [jnp.ones((1,), bool), s[1:] != s[:-1]])
                u = jnp.sort(jnp.where(uniq, s, big))
                u = u[:vcap] if u.shape[0] >= vcap else jnp.pad(
                    u, (0, vcap - u.shape[0]), constant_values=big)
                return jnp.concatenate(
                    [jnp.stack([kmin, kmax, n_valid, dup]), u])
            return f

        b_arrays = _dev_arrays(build)
        b_arrays = encode_key_arrays(b_arrays, build, bk, self.string_dicts)
        fn = _cached_program(f"bjoin-dense-stats|{vcap}|" + fp, build_stats)
        stats = fn(b_arrays, build.sel, np.int32(build.num_rows))
        # inside a fused region this STAGES the vector for the region's
        # single batched prologue fetch; outside (fusion off) it is the
        # same copy_to_host_async overlap the per-op path always had
        stage_scalars((skey, id(build)), stats)
        # the batch rides in the list so its id cannot be recycled while
        # the prefetch is outstanding (same discipline as _bfast_cache);
        # slot 4 memoizes the host copy so stats + DPP values cost ONE
        # round trip between them
        self._dense_pending = [id(build), build, stats, b_arrays, None]
        if memo is not None:
            memo[mkey] = self._dense_pending

    def _pending_host(self, pending):
        if pending[4] is None:
            r = current_region()
            skey = getattr(self, "_dense_stats_key", None)
            if r is not None and skey is not None:
                # region path: the batched prologue fetch resolves EVERY
                # staged stats vector in one sync; this join's is keyed
                # by (program identity, build identity)
                pending[4] = r.resolve((skey, pending[0]), pending[2])
            else:
                pending[4] = fetch(pending[2])  # fusion-ok (per-op path: the one stats sync this join pays)
            # a cache-resident build remembers its probed stats: the
            # NEXT query reusing this build skips the dispatch and this
            # blocking fetch entirely (see _dense_prefetch)
            ent = getattr(self, "_cache_entry", None)
            skey = getattr(self, "_dense_stats_key", None)
            if ent is not None and skey is not None:
                ent.put_stat(skey, pending[4])
        return pending[4]

    def _dense_build_state(self, build: ColumnBatch, conf):
        """Resolve (kmin, table) once per build batch; None if the dense
        path does not apply (dup keys / unbounded domain / host payload)."""
        cache = getattr(self, "_dense_cache", None)
        if cache is not None and cache[0] == id(build):
            return cache[2]
        self._dense_prefetch(build, conf)
        pending = getattr(self, "_dense_pending", None)
        state = None
        if pending is not None and pending[0] == id(build):
            cap = conf["spark.rapids.tpu.join.denseDomainCap"]
            # stats survive for the CSR match path, tagged with the
            # batch identity + side (valid for the compacted build too:
            # same live rows — execute() re-tags after compaction)
            self._dense_stats_host = (id(build), self.build_side,
                                      self._pending_host(pending))
            payload = self._dense_payload_fields(build)
            if payload is not None:
                state = self._dense_build_state_impl(
                    build, cap, payload, self._dense_stats_host[2],
                    pending[3])
        self._dense_pending = None
        self._dense_cache = (id(build), build, state)
        return state

    def _dense_build_state_impl(self, build, domain_cap, payload_idxs,
                                stats, b_arrays):
        lk, rk, common = self._bound_keys()
        bk = rk if self.build_side == 1 else lk
        ct = common[0]
        ik = _int_key_caster(ct)
        fp = self._fingerprint() + f"|dense|bs{self.build_side}"
        kmin, kmax, n_valid, dup = [int(x) for x in stats[:4]]
        if n_valid == 0 or dup > 0:
            return None
        domain = kmax - kmin + 1
        if domain <= 0 or domain > domain_cap:
            return None
        D = bucket_capacity(domain)

        def build_table():
            @program("bjoin_dense_table")
            def g(b_arrays, sel, kmin_s, n_build):
                b_cap = next(a[0].shape[0] for a in b_arrays
                             if a is not None)
                active = jnp.arange(b_cap, dtype=jnp.int32) < n_build
                if sel is not None:
                    active = active & sel
                d, ok = _eval_int_key(bk[0], b_arrays, b_cap, n_build,
                                      ct, ik, active=active)
                idx = jnp.where(ok, d.astype(jnp.int64) - kmin_s,
                                jnp.int64(D))
                return jnp.full((D,), -1, jnp.int32).at[idx].set(
                    jnp.arange(b_cap, dtype=jnp.int32), mode="drop")
            return g

        gfn = _cached_program(f"bjoin-dense-table|{fp}|{D}", build_table)
        table = gfn(b_arrays, build.sel, jnp.int64(kmin),
                    np.int32(build.num_rows))
        pay = []
        dicts = {}
        for i in payload_idxs:
            c = build.columns[i]
            if isinstance(c, DeviceColumn):
                pay.append((c.data, c.valid))
                continue
            if isinstance(c, DictStringColumn):
                # already device dictionary codes (e.g. output of an
                # upstream dense join): reuse verbatim, zero round trips
                pay.append((c.codes, c.valid))
                dicts[i] = c.dictionary
                continue
            # string payload: factorize on host once (memoized on the
            # column), upload int32 codes — nulls carry code 0 under a
            # FALSE validity mask (the mask, not the code, marks null)
            jcodes, jvalid, dct = _encode_host_string(c)
            pay.append((jcodes, jvalid))
            dicts[i] = dct
        return {"table": table, "kmin": kmin, "D": D, "ct": ct, "ik": ik,
                "payload_idxs": payload_idxs, "payload": tuple(pay),
                "payload_dicts": dicts}

    def _sorted_join_pair(self, m, probe: ColumnBatch, build: ColumnBatch):
        """One streamed batch against the sorted (or CSR) build, None
        where the batch holds no row.  The join kernel treats every row
        below num_rows as live, and a streamed batch may carry a selection
        mask from an upstream filter or join, so it is compacted first
        (the shuffle path compacts inside the exchange): that is this
        join's work and inside its span; compact's own live count doubles
        as the empty check (one sync, not two)."""
        with counted_span("join_exec_s", self.op_id, "join:pair", "join"):
            if probe.sel is not None:
                probe = batch_utils.compact(probe)
            if probe.num_rows == 0:
                return None
            if self.build_side == 1:
                return self._join_pair_impl(m, probe, build)
            return self._join_pair_impl(m, build, probe)

    def _dense_join_pair(self, ctx, m, probe: ColumnBatch,
                         build: ColumnBatch):
        with counted_span("join_exec_s", self.op_id, "join:pair", "join"):
            state = self._dense_build_state(build, ctx.conf)
            if state is None:
                return None
            self._count_semi_anti()
            return self._dense_probe(m, probe, build, state)

    def _dense_probe(self, m, probe: ColumnBatch, build: ColumnBatch,
                     state):
        how = self.how
        lk, rk, common = self._bound_keys()
        pk = lk if self.build_side == 1 else rk
        ct, ik, D = state["ct"], state["ik"], state["D"]
        has_sel = probe.sel is not None
        fp = (self._fingerprint()
              + f"|denseprobe|bs{self.build_side}|{how}|{D}|"
              + f"sel{int(has_sel)}")

        def build_probe():
            @program("bjoin_dense_probe")
            def h(p_arrays, table, payload, kmin_s, n_probe, sel):
                p_cap = next(a[0].shape[0] for a in p_arrays
                             if a is not None)
                active = jnp.arange(p_cap, dtype=jnp.int32) < n_probe
                if sel is not None:
                    active = active & sel
                d, ok = _eval_int_key(pk[0], p_arrays, p_cap, n_probe, ct,
                                      ik, active=active)
                ok = ok & active
                idx = d.astype(jnp.int64) - kmin_s
                in_dom = ok & (idx >= 0) & (idx < D)
                safe = jnp.clip(idx, 0, D - 1).astype(jnp.int32)
                bi = jnp.where(in_dom, table[safe], -1)
                matched = bi >= 0
                if how == "semi":
                    return matched, ()
                if how == "anti":
                    return active & ~matched, ()
                if how == "existence":
                    return active, ((matched, None),)
                safe_bi = jnp.clip(bi, 0, None)
                cols = []
                for bd, bv in payload:
                    gv = matched if bv is None else (matched & bv[safe_bi])
                    cols.append((bd[safe_bi], gv))
                sel_out = matched if how == "inner" else active
                return sel_out, tuple(cols)
            return h

        fn = _cached_program(fp, build_probe)
        p_arrays = _dev_arrays(probe)
        p_arrays = encode_key_arrays(p_arrays, probe, pk, self.string_dicts)
        with m.time("opTime"):
            sel_out, pay_cols = fn(p_arrays, state["table"],
                                   state["payload"], jnp.int64(state["kmin"]),
                                   np.int32(probe.num_rows), probe.sel)
        if how in ("semi", "anti"):
            out = ColumnBatch(self._schema, probe.columns, probe.num_rows,
                              sel_out)
            self._dense_metrics(m, out)
            return out
        if how == "existence":
            md, _ = pay_cols[0]
            exists = DeviceColumn(T.BOOLEAN, md, None)
            out = ColumnBatch(self._schema,
                              list(probe.columns) + [exists],
                              probe.num_rows, sel_out)
            self._dense_metrics(m, out)
            return out
        build_cols = {}
        pdicts = state.get("payload_dicts") or {}
        for i, (bd, bv) in zip(state["payload_idxs"], pay_cols):
            f = build.schema.fields[i]
            if i in pdicts:
                # gathered dictionary codes stay ON DEVICE as a
                # DictStringColumn; the decode (one fetch) happens only
                # if a downstream consumer touches .array
                build_cols[f.name] = DictStringColumn(bd, bv, pdicts[i])
            else:
                build_cols[f.name] = DeviceColumn(f.dtype, bd, bv)
        using = set(self.using)
        cols: List = []
        if self.build_side == 1:
            cols.extend(probe.columns)
            for f in build.schema:
                if f.name not in using:
                    cols.append(build_cols[f.name])
        else:
            for f in build.schema:
                cols.append(build_cols[f.name])
            for f, c in zip(probe.schema, probe.columns):
                if f.name not in using:
                    cols.append(c)
        out = ColumnBatch(self._schema, cols, probe.num_rows, sel_out)
        if self.condition is not None:
            out = self._apply_residual(out)
        self._dense_metrics(m, out)
        return out

    @staticmethod
    def _dense_metrics(m, out: ColumnBatch) -> None:
        """The dense path is sync-free, so exact numOutputRows (a device
        reduction over the selection mask) is only paid for at DEBUG
        metric level; batch counts are always recorded."""
        m.add("numOutputBatches", 1)
        if m.level == "DEBUG":
            m.add("numOutputRows", out.row_count())

    # -- dynamic partition pruning ------------------------------------------------
    #
    # GpuSubqueryBroadcastExec / GpuDynamicPruningExpression analog: the
    # broadcast build side IS the subquery result — once it materializes,
    # its key range (and exact key list when small) becomes a runtime
    # predicate on the probe-side scan, reaching parquet file/row-group
    # and hive-partition pruning before any probe row is decoded.

    def _inject_dpp(self, ctx, build: ColumnBatch) -> None:
        conf = ctx.conf
        if not conf["spark.rapids.tpu.sql.dpp.enabled"]:
            return
        if self.how not in ("inner", "semi"):
            return  # pruning probe rows would change left/right/full/anti
        pending = getattr(self, "_dense_pending", None)
        if pending is None or pending[0] != id(build):
            return
        lk, rk, common = self._bound_keys()
        ct = common[0]
        try:
            kind = np.dtype(ct.numpy_dtype).kind
        except TypeError:
            return
        if kind not in "iu":  # ints and dates (int32 days) only
            return
        probe_side = 1 - self.build_side
        pk = (lk if self.build_side == 1 else rk)[0]
        from .planner import strip_alias
        from ..exprs import BoundReference
        core = strip_alias(pk)
        if not isinstance(core, BoundReference):
            return
        pname = self.children[probe_side].output_schema.names()[core.ordinal]
        target = _scan_origin(self.children[probe_side], pname)
        if target is None:
            return
        scan, scol = target
        max_in = conf["spark.rapids.tpu.sql.dpp.maxInKeys"]

        def preds_fn():
            # deferred to the scan's first read (_effective_source): by
            # then every join above the scan has staged its build stats,
            # so inside a fused region this resolution rides ONE batched
            # prologue fetch for the whole chain
            host = self._pending_host(pending)
            kmin, kmax, n_valid, dup = [int(x) for x in host[:4]]

            def values_fn():
                big = np.iinfo(np.int64).max
                vals = host[4:]
                vals = vals[vals != big]
                return vals.tolist() if len(vals) <= max_in else None

            return _runtime_key_preds(scol, ct, kmin, kmax, n_valid,
                                      n_valid - dup, conf, values_fn)

        scan.runtime_predicates = preds_fn

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        m = ctx.metric_set(self.op_id)
        # the dense-stats helpers run deep below execute with only conf
        # in hand; the context rides on the node for the query-scoped
        # stats memo (cleared in the finally — prepared-statement clones
        # are per-run, so this never leaks across executions)
        self._exec_ctx = ctx
        probe_side = 1 - self.build_side
        dense_ok = self._dense_static_ok(ctx.conf)
        # dense builds keep the selection mask (the build programs fold
        # it in): the live-count round trip is paid only on fallback
        bh = self.children[self.build_side].materialize(
            ctx, compact=not dense_ok)
        # broadcast-tier cache hit: the entry rides along so the dense
        # prefetch can reuse (and deposit) probed build stats
        self._cache_entry = getattr(bh, "cache_entry", None)
        pgen = self.children[probe_side].execute(ctx)
        try:
            build = bh.get()
            if dense_ok:
                self._dense_prefetch(build, ctx.conf)
                self._inject_dpp(ctx, build)
            for probe in pgen:
                if probe.num_rows == 0:
                    continue
                if dense_ok:
                    # sync-free: folds any upstream selection mask into
                    # the probe program instead of compacting
                    out = self._dense_join_pair(ctx, m, probe, build)
                    if out is not None:
                        yield out
                        continue
                    # dense rejected at runtime: the sorted kernels need
                    # a compacted build — pay the sync once, and re-tag
                    # the surviving stats to the compacted twin
                    if build.sel is not None:
                        old_build = build
                        build = batch_utils.compact(build)
                        st = getattr(self, "_dense_stats_host", None)
                        if st is not None and st[0] == id(old_build):
                            self._dense_stats_host = (id(build), st[1],
                                                      st[2])
                        dense_ok = False
                        if build.num_rows == 0 and self.how in (
                                "inner", "semi"):
                            return
                out = self._sorted_join_pair(m, probe, build)
                if out is not None:
                    yield out
        finally:
            # close the suspended probe generator deterministically: a DCN
            # exchange below holds collective barriers in its cleanup that
            # must not wait for garbage collection
            pgen.close()
            bh.close()
            # drop device-array pins (build batch, dense table, payload,
            # sorted-key caches) so the spill catalog can reclaim the HBM
            # while later plan stages run
            self._dense_cache = None
            self._dense_pending = None
            self._bfast_cache = None
            self._csr_cache = None
            self._dense_stats_host = None
            self._cache_entry = None
            self._exec_ctx = None


def _float_orderable(d, ik):
    """Total-order injective int image of a float key array: -0.0
    normalized to +0.0, NaN canonicalized to one bit pattern whose image
    no non-NaN float maps to, then the sign-magnitude flip.  THE single
    implementation — the dense path and the sorted searchsorted path must
    agree on which float keys are equal (Spark NaN==NaN, -0.0==0.0 join
    semantics).

    float64 uses the arithmetic bit extraction (hashing.f64_bit_pattern):
    XLA's X64-rewrite pass on real TPU backends implements no 64-bit
    bitcast-convert.  Its canonical NaN (0x7FF8..) flips to an image
    strictly above +inf's, so the NaN slot stays unique; the int64-max
    sentinel would require a -0.0 pattern, normalized away, so it too
    stays unique."""
    if d.dtype == jnp.float64:
        from ..ops.hashing import f64_bit_pattern
        b = f64_bit_pattern(d)  # -0.0 -> +0.0 bits, NaN -> 0x7FF8.., FTZ
    else:
        z = jnp.where(d == 0.0, jnp.zeros_like(d), d)
        b = jax.lax.bitcast_convert_type(z, ik)
        mx = np.array(np.iinfo(ik).max, dtype=ik)
        b = jnp.where(jnp.isnan(d), mx, b)
    mn = np.array(np.iinfo(ik).min, dtype=ik)
    return jnp.where(b < 0, ~b, b | mn)


def _runtime_key_preds(scol: str, ct, kmin: int, kmax: int,
                       n_valid: int, n_distinct: int, conf,
                       values_fn) -> list:
    """Shared predicate construction for runtime join filters (DPP and
    the SMJ bloom-filter analog): empty build short-circuits the scan,
    small distinct sets push an exact IN-list, otherwise the key range.
    ``values_fn() -> list`` supplies int key images lazily."""
    is_date = ct.kind == T.TypeKind.DATE

    def conv(v):
        if is_date:
            import datetime as _dt
            return _dt.date(1970, 1, 1) + _dt.timedelta(days=int(v))
        return int(v)

    if n_valid == 0:
        return [(scol, "in", [])]
    preds = [(scol, ">=", conv(kmin)), (scol, "<=", conv(kmax))]
    max_in = conf["spark.rapids.tpu.sql.dpp.maxInKeys"]
    if 0 < n_distinct <= max_in and values_fn is not None:
        vals = values_fn()
        if vals is not None and len(vals) <= max_in:
            preds = [(scol, "in", [conv(v) for v in vals])]
    return preds


def _scan_origin(node, out_name: str):
    """Trace an output column through Coalesce/Stage chains to the scan
    column it passes through from, or None when any step computes it.
    Returns (ScanExec, scan_column_name)."""
    from .coalesce import CoalesceBatchesExec
    from .physical import ScanExec, StageExec
    from .planner import strip_alias
    from ..exprs import BoundReference
    name = out_name
    while True:
        from .fusion import FusedRegionExec
        if isinstance(node, (CoalesceBatchesExec, FusedRegionExec)):
            node = node.children[0]
            continue
        if isinstance(node, StageExec):
            cur = list(node.children[0].output_schema.names())
            maps = []  # forward per-project mapping out -> in
            for kind, payload in node.steps:
                if kind != "project":
                    continue
                mp = {}
                new_names = []
                for entry in payload:
                    pname, expr = entry[0], entry[1]
                    new_names.append(pname)
                    if expr is None:
                        continue  # host passthrough (strings) — not keys
                    core = strip_alias(expr)
                    if isinstance(core, BoundReference) \
                            and core.ordinal < len(cur):
                        mp[pname] = cur[core.ordinal]
                maps.append(mp)
                cur = new_names
            for mp in reversed(maps):
                name = mp.get(name)
                if name is None:
                    return None
            node = node.children[0]
            continue
        if isinstance(node, ScanExec):
            return (node, name) if name in node.output_schema else None
        return None


def _int_key_caster(ct) -> Optional[np.dtype]:
    """Physical int dtype an equi-key of type ``ct`` maps into for dense
    direct addressing (strings ride as int32 dictionary codes, floats as
    total-order bit patterns), or None when no injective int image exists."""
    if ct.is_string:
        return np.dtype(np.int32)
    try:
        np_dt = np.dtype(ct.numpy_dtype)
    except TypeError:
        return None
    if np_dt.kind in "iu":
        return np_dt
    if np_dt.kind == "f":
        return np.dtype(np.int32) if np_dt.itemsize == 4 \
            else np.dtype(np.int64)
    return None


def _eval_int_key(expr, arrays, cap, n_rows, ct, ik, active=None):
    """Evaluate a bound key expression to (int image, valid mask) inside a
    jitted program.  The float mapping matches _match_state's orderable():
    -0.0 normalized, NaN canonicalized to the all-ones image."""
    if active is None:
        active = jnp.arange(cap, dtype=jnp.int32) < n_rows
    ectx = EvalContext(list(arrays), cap, active=active)
    d, v = expr.eval(ectx)
    if not ct.is_string:
        d = promote_physical(d, expr.dtype, ct)
    ok = active if v is None else (active & v)
    np_dt = None if ct.is_string else np.dtype(ct.numpy_dtype)
    if np_dt is not None and np_dt.kind == "f":
        d = _float_orderable(d, ik)
    return d, ok


def _dense_key_slot(expr, arrays, cap, n_rows, ct, ik, kmin_s, D,
                    sel=None):
    """THE shared mask-and-index idiom of every dense kernel: fold the
    selection mask into the active set, evaluate the int key image, and
    produce (slot index, valid mask, in-domain mask).  Build kernels
    scatter with `where(ok, idx, D)` + mode=drop; probe kernels gather
    with `clip(idx)` guarded by in_dom.  One definition so a fix to key
    imaging or null folding can never diverge across paths."""
    active = jnp.arange(cap, dtype=jnp.int32) < n_rows
    if sel is not None:
        active = active & sel
    d, ok = _eval_int_key(expr, arrays, cap, n_rows, ct, ik,
                          active=active)
    idx = d.astype(jnp.int64) - kmin_s
    in_dom = ok & (idx >= 0) & (idx < D)
    return idx, ok, in_dom


def _count_expansion(total: int, out_cap: int) -> None:
    """One expansion, in the running query's ``QueryStats``: the candidate
    pairs it was sized for (the count the host has just read) and the
    slots it runs at."""
    stats = QueryStats.get()
    stats.join_pairs += int(total)
    stats.join_out_slots += int(out_cap)


def _has_broadcast_hint(node) -> bool:
    """True when the subtree carries a broadcast hint, looking through
    row-shaping unary operators the user may have stacked above it
    (Spark's ResolvedHint survives filters/projections the same way)."""
    from . import logical as L
    while node is not None:
        if getattr(node, "broadcast_hint", False):
            return True
        if isinstance(node, (L.Filter, L.Project, L.Limit)) and node.children:
            node = node.children[0]
            continue
        return False
    return False


def _legal_build_sides(how: str) -> tuple:
    """Sides that may be broadcast (must not be the row-preserving side).
    full outer never broadcasts; inner/cross are symmetric."""
    return {"inner": (1, 0), "cross": (1, 0), "left": (1,), "semi": (1,),
            "anti": (1,), "existence": (1,), "right": (0,),
            "full": ()}[how]


def plan_broadcast_join(plan, left: TpuExec, right: TpuExec, conf,
                        shared_dicts: dict) -> Optional[BroadcastJoinExec]:
    """Choose a broadcast join when legal and the build side is small.

    Selection mirrors the reference (GpuBroadcastHashJoinExecBase meta +
    spark.sql.autoBroadcastJoinThreshold): an explicit ``broadcast()`` hint
    on a legal side wins; otherwise the smallest side estimated under
    spark.rapids.tpu.sql.autoBroadcastJoinThreshold bytes builds.  A hint
    on a row-preserving side (e.g. the left of a left outer join) cannot
    be honored and the join shuffles."""
    how = _canon_how(plan.how)
    legal = _legal_build_sides(how)
    if not legal:
        return None
    hints = [_has_broadcast_hint(plan.children[i]) for i in (0, 1)]
    build_side = next((s for s in legal if hints[s]), None)
    if build_side is None:
        if any(hints):
            return None  # hint only on an illegal side
        threshold = conf["spark.rapids.tpu.sql.autoBroadcastJoinThreshold"]
        if threshold < 0:
            return None
        ests = [_estimated_bytes(plan.children[i]) for i in (0, 1)]
        fits = [s for s in legal
                if ests[s] is not None and ests[s] <= threshold]
        if not fits:
            return None
        build_side = min(fits, key=lambda s: ests[s])
    from .cbo import estimate_rows
    probe_est = estimate_rows(plan.children[1 - build_side])
    if build_side == 1:
        out = BroadcastJoinExec(plan, left, BroadcastExchangeExec(right),
                                conf, 1, string_dicts=shared_dicts)
    else:
        out = BroadcastJoinExec(plan, BroadcastExchangeExec(left), right,
                                conf, 0, string_dicts=shared_dicts)
    out.probe_est_rows = probe_est
    return out


def _estimated_bytes(logical) -> Optional[float]:
    from ..batch import estimated_row_bytes
    from .cbo import estimate_rows
    rows = estimate_rows(logical)
    if rows is None:
        return None
    return rows * estimated_row_bytes(logical.schema())


# ---------------------------------------------------------------------------------
# gather helpers
# ---------------------------------------------------------------------------------

def _dev_arrays(batch: ColumnBatch):
    return tuple((c.data, c.valid) if isinstance(c, DeviceColumn) else None
                 for c in batch.columns)


def _gather_cols(batch: ColumnBatch, idx: jax.Array, valid_if: Optional[str]):
    """Gather rows of ``batch`` by (possibly -1) indices.

    valid_if="neg_is_null": idx < 0 produces a null row (outer join padding).
    Returns {"cols": [...], "idx": idx}.
    """
    null_rows = (idx < 0) if valid_if == "neg_is_null" else None
    bad_idx = (idx < 0) | (idx >= batch.num_rows)
    safe = jnp.clip(idx, 0, batch.capacity - 1)
    host_idx = None
    out: List = []
    for f, c in zip(batch.schema, batch.columns):
        if isinstance(c, DictStringColumn):
            codes = c.codes[safe]
            valid = c.valid[safe] if c.valid is not None else None
            valid = (~bad_idx) if valid is None else (valid & ~bad_idx)
            out.append(DictStringColumn(codes, valid, c.dictionary))
            continue
        if isinstance(c, HostStringColumn) and f.dtype.is_string:
            # dictionary-encode ONCE per source column (cached on the
            # immutable column object), then every join output is a
            # device int32 gather carrying a DictStringColumn — the
            # pre-r5 path fetched the index array and arrow-took per
            # output batch
            jcodes, jvalid, dct = _encode_host_string(c)
            codes = jcodes[safe]
            valid = jvalid[safe] if jvalid is not None else None
            valid = (~bad_idx) if valid is None else (valid & ~bad_idx)
            out.append(DictStringColumn(codes, valid, dct))
            continue
        if isinstance(c, HostStringColumn):
            import pyarrow as pa
            # nested/other host-carried types: fetch + arrow take,
            # index fetch shared across all such columns in this gather
            if host_idx is None:
                np_idx = fetch(idx).astype(np.int64, copy=True)
                bad = (np_idx < 0) | (np_idx >= batch.num_rows)
                np_idx[bad] = 0
                host_idx = pa.array(np_idx, type=pa.int64(), mask=bad)
            out.append(HostStringColumn(c.array.take(host_idx)))
            continue
        data = _take(c.data, safe)
        valid = c.valid[safe] if c.valid is not None else None
        if null_rows is not None:
            valid = (~null_rows) if valid is None else (valid & ~null_rows)
        out.append(DeviceColumn(f.dtype, data, valid))
    return {"cols": out, "idx": idx}


@program("join_take64")
def _take64(data, idx):
    words = jax.lax.bitcast_convert_type(data, jnp.uint32)   # [n, 2]
    return jax.lax.bitcast_convert_type(words[idx], data.dtype)


def _take(data, idx):
    """``data[idx]`` (``idx`` in bounds).  A 64-bit integer column goes as
    rows of two 32-bit words: the chip gathers the plain form as its two
    halves apart, and only one of the halves' sources gets the fast memory.
    Out of 1,048,576 rows into 16,777,216 slots the plain form took 528 to
    641 ms by the index pattern, and 5% more or less by the process; this
    one 103 ms, whatever the pattern (my chip runs, PR 35).  The chip has
    no such view of a float64."""
    if data.ndim == 1 and data.dtype in (jnp.int64, jnp.uint64):
        return _take64(data, idx)
    return data[idx]


def _encode_host_string(c: HostStringColumn):
    # -> (device int32 codes, device validity-or-None, arrow dictionary),
    # memoized on the (immutable) column object
    cached = getattr(c, "_dict_enc_cache", None)
    if cached is not None:
        return cached
    import pyarrow as pa
    arr = c.array
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    denc = arr.dictionary_encode()
    codes_np = denc.indices.to_numpy(zero_copy_only=False)
    if arr.null_count > 0:
        valid_np = np.asarray(arr.is_valid())
        codes_np = np.where(valid_np, codes_np, 0).astype(np.int32)
        jvalid = jnp.asarray(valid_np)
    else:
        codes_np = codes_np.astype(np.int32)
        jvalid = None
    enc = (jnp.asarray(codes_np), jvalid, denc.dictionary)
    c._dict_enc_cache = enc
    return enc


def _empty_batch(schema: Schema) -> ColumnBatch:
    cap = bucket_capacity(0)
    cols: List = []
    for f in schema:
        if f.dtype.is_string:
            import pyarrow as pa
            cols.append(HostStringColumn(pa.nulls(cap, type=pa.string())))
        else:
            cols.append(DeviceColumn(
                f.dtype, jnp.zeros((cap,), dtype=f.dtype.numpy_dtype),
                jnp.zeros((cap,), dtype=bool)))
    return ColumnBatch(schema, cols, 0)

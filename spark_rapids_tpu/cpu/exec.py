"""CPU fallback physical operators (pandas/Arrow host execution).

When the planner tags a logical node as not-TPU-runnable (string compute,
exotic types, unsupported corner), the node executes here.  Children may
still run on TPU — the batch boundary is the host↔device transition, exactly
like the reference's GpuColumnarToRowExec / GpuRowToColumnarExec insertions
(GpuTransitionOverrides.scala:50-116).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Tuple

import numpy as np

from .. import types as T
from ..batch import ColumnBatch, Schema, from_arrow, to_arrow
from ..exprs import bind
from ..plan import logical as L
from ..plan.physical import ExecContext, TpuExec
from .eval import eval_cpu

__all__ = ["CpuOpExec", "arrow_to_values", "values_to_arrow"]


def arrow_to_values(table, schema: Schema):
    """Arrow table → list of (numpy data, valid) pairs (dense rows)."""
    vals = []
    for f, col in zip(schema, table.columns):
        arr = col.combine_chunks() if hasattr(col, "combine_chunks") else col
        if f.dtype.is_string:
            data = np.array(arr.to_pylist(), dtype=object)
            valid = np.array([x is not None for x in data], dtype=bool)
            vals.append((data, None if valid.all() else valid))
            continue
        import pyarrow as pa
        valid = np.asarray(arr.is_valid()) if arr.null_count else None
        if arr.null_count and not f.dtype.is_floating and not f.dtype.is_decimal:
            import datetime as _dtm
            if pa.types.is_date(arr.type):
                zero = pa.scalar(_dtm.date(1970, 1, 1), type=arr.type)
            elif pa.types.is_timestamp(arr.type):
                zero = pa.scalar(_dtm.datetime(1970, 1, 1), type=arr.type)
            else:
                zero = pa.scalar(0).cast(arr.type)
            arr = arr.fill_null(zero)
        np_arr = arr.to_numpy(zero_copy_only=False)
        if f.dtype.kind == T.TypeKind.DATE:
            np_arr = np_arr.astype("datetime64[D]").astype(np.int32)
        elif f.dtype.kind == T.TypeKind.TIMESTAMP:
            np_arr = np_arr.astype("datetime64[us]").astype(np.int64)
        elif f.dtype.is_decimal:
            # scaled ints; beyond 64-bit range keep python ints (object) —
            # exact compare/sort, no overflow (decimal128 fallback tier)
            kind = object if f.dtype.precision > 18 else np.int64
            np_arr = np.array([0 if x is None else int(x.scaleb(f.dtype.scale))
                               for x in arr.to_pylist()], dtype=kind)
        else:
            np_arr = np_arr.astype(f.dtype.numpy_dtype)
        vals.append((np.ascontiguousarray(np_arr), valid))
    return vals


def _py_scalar(v):
    """numpy scalar → plain python (arrow list building wants natives)."""
    return v.item() if hasattr(v, "item") else v


def values_to_arrow(schema: Schema, values, n: int):
    import pyarrow as pa
    from ..batch import logical_to_arrow
    arrays = []
    for f, (data, valid) in zip(schema, values):
        mask = None if valid is None else ~valid
        if f.dtype.is_nested:
            pl = [None if (mask is not None and mask[i]) else data[i]
                  for i in range(n)]
            arrays.append(pa.array(pl, type=logical_to_arrow(f.dtype)))
        elif f.dtype.is_string:
            pl = [None if (mask is not None and mask[i]) else data[i]
                  for i in range(n)]
            arrays.append(pa.array(pl, type=pa.string()))
        elif f.dtype.kind == T.TypeKind.DATE:
            arrays.append(pa.array(data[:n].astype("datetime64[D]"),
                                   type=pa.date32(), mask=mask))
        elif f.dtype.kind == T.TypeKind.TIMESTAMP:
            arrays.append(pa.array(data[:n].astype("datetime64[us]"),
                                   type=pa.timestamp("us"), mask=mask))
        elif f.dtype.is_decimal:
            from decimal import Decimal
            pl = [None if (mask is not None and mask[i])
                  else Decimal(int(data[i])).scaleb(-f.dtype.scale)
                  for i in range(n)]
            arrays.append(pa.array(pl, type=logical_to_arrow(f.dtype)))
        else:
            arrays.append(pa.array(data[:n], type=logical_to_arrow(f.dtype),
                                   mask=mask))
    return pa.table(dict(zip(schema.names(), arrays)))


def sort_table(table, schema: Schema, orders):
    """``table`` ordered by ``orders`` ((bound expression, ascending, nulls
    first) triples) on the host: the CPU Sort, and the device sort's way
    with a few rows under a string key (plan/exec_nodes.SortExec)."""
    import pyarrow as pa
    vals = arrow_to_values(table, schema)
    n = table.num_rows
    # lexicographic: apply np.argsort stably from minor to major key
    perm = np.arange(n)
    for expr, ascending, nulls_first in reversed(orders):
        d, v = eval_cpu(expr, vals, n)
        d2, v2 = d[perm], (v[perm] if v is not None else None)
        keys = CpuOpExec._sort_key(d2, v2, ascending, nulls_first)
        perm = perm[np.argsort(keys, kind="stable")]
    return table.take(pa.array(perm))


class CpuOpExec(TpuExec):
    """Executes one logical operator on host over its children's output."""

    def __init__(self, plan: L.LogicalPlan, children: List[TpuExec]):
        super().__init__(children)
        self.plan = plan

    @property
    def output_schema(self) -> Schema:
        return self.plan.schema()

    def node_desc(self):
        return f"CpuFallback[{self.plan.node_desc()}]"

    def _child_table(self, ctx: ExecContext, i: int = 0):
        import pyarrow as pa
        tables = [to_arrow(b) for b in self.children[i].execute(ctx)]
        if not tables:
            sch = self.children[i].output_schema
            from ..batch import logical_to_arrow
            return pa.table({f.name: pa.array([], type=logical_to_arrow(f.dtype))
                             for f in sch})
        return pa.concat_tables(tables)

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        from .eval import set_ansi
        from ..udf import _isolation, set_isolation
        # save/restore: nested CpuOpExec children run (and finish) inside
        # the parent's _run, and must not reset the parent's settings
        prev_iso = _isolation()
        set_ansi(ctx.conf["spark.rapids.tpu.sql.ansi.enabled"])
        set_isolation(
            ctx.conf["spark.rapids.tpu.python.worker.isolation"],
            ctx.conf["spark.rapids.tpu.python.worker.timeout"])
        try:
            table = self._run(ctx)
        finally:
            set_ansi(False)
            set_isolation(*prev_iso)
        min_cap = ctx.conf["spark.rapids.tpu.sql.minBatchCapacity"]
        batch_rows = ctx.conf["spark.rapids.tpu.sql.batchSizeRows"]
        for off in range(0, max(table.num_rows, 1), batch_rows):
            chunk = table.slice(off, min(batch_rows, table.num_rows - off)) \
                if table.num_rows else table
            yield from_arrow(chunk, min_capacity=min_cap, device=ctx.device)
            if not table.num_rows:
                break

    # -- per-op host implementations ---------------------------------------------
    def _run(self, ctx: ExecContext):
        p = self.plan
        if isinstance(p, L.Project):
            return self._run_project(ctx, p)
        if isinstance(p, L.Filter):
            return self._run_filter(ctx, p)
        if isinstance(p, L.Aggregate):
            return self._run_aggregate(ctx, p)
        if isinstance(p, L.Sort):
            return self._run_sort(ctx, p)
        if isinstance(p, L.Join):
            return self._run_join(ctx, p)
        if isinstance(p, L.Distinct):
            return self._child_table(ctx).group_by(
                self.children[0].output_schema.names()).aggregate([])
        if isinstance(p, L.Window):
            return self._run_window(ctx, p)
        if isinstance(p, L.Generate):
            t = self._child_table(ctx)
            pdf = t.to_pandas()
            col = pdf[p.column]
            # classify SOURCE rows before exploding: plain EXPLODE drops
            # rows from empty/null ARRAYS but must keep null ELEMENTS
            # (matching Spark and the device GenerateExec)
            def _arr_len(a):
                return 0 if a is None else len(a)
            no_rows = col.isna() | (col.map(_arr_len) == 0)
            out = pdf.explode(p.column)
            if not p.outer:
                out = out[~out.index.isin(pdf.index[no_rows])]
            out = out.reset_index(drop=True)
            out = out.rename(columns={p.column: p.out_name})
            import pyarrow as pa
            from ..batch import logical_to_arrow
            sch = p.schema()
            return pa.table({
                f.name: pa.array(out[f.name],
                                 type=logical_to_arrow(f.dtype),
                                 from_pandas=True)
                for f in sch})
        if isinstance(p, L.Sample):
            t = self._child_table(ctx)
            rng = np.random.default_rng(p.seed)
            keep = rng.random(t.num_rows) < p.fraction
            return t.filter(keep)
        if isinstance(p, L.Limit):
            t = self._child_table(ctx)
            off = getattr(p, "offset", 0) or 0
            return t.slice(off, p.n)
        if isinstance(p, L.Union):
            import pyarrow as pa
            parts = [self._child_table(ctx, i)
                     for i in range(len(self.children))]
            return pa.concat_tables(parts, promote_options="default")
        raise NotImplementedError(
            f"CPU fallback for {type(p).__name__} not implemented")

    def _run_project(self, ctx, p: L.Project):
        in_schema = self.children[0].output_schema
        table = self._child_table(ctx)
        vals = arrow_to_values(table, in_schema)
        n = table.num_rows
        outs = []
        for name, e in p.exprs:
            b = bind(e, in_schema)
            outs.append(eval_cpu(b, vals, n))
        return values_to_arrow(p.schema(), outs, n)

    def _run_filter(self, ctx, p: L.Filter):
        import pyarrow as pa
        in_schema = self.children[0].output_schema
        table = self._child_table(ctx)
        vals = arrow_to_values(table, in_schema)
        n = table.num_rows
        d, v = eval_cpu(bind(p.condition, in_schema), vals, n)
        keep = d if v is None else (d & v)
        return table.filter(pa.array(keep))

    def _run_aggregate(self, ctx, p: L.Aggregate):
        import pandas as pd
        from .. import aggfns as A
        from ..plan.planner import strip_alias
        in_schema = self.children[0].output_schema
        table = self._child_table(ctx)
        vals = arrow_to_values(table, in_schema)
        n = table.num_rows

        key_vals = []
        for name, e in p.group_exprs:
            b = bind(e, in_schema)
            key_vals.append((name, b, eval_cpu(b, vals, n)))
        agg_specs = []
        for name, e in p.agg_exprs:
            b = strip_alias(bind(e, in_schema))
            child_vals = ([eval_cpu(c, vals, n) for c in b.children]
                          if b.children else [(np.ones(n), None)])
            agg_specs.append((name, b, child_vals))

        if not key_vals:
            outs = [self._agg_scalar(b, cv, n) for _, b, cv in agg_specs]
            return values_to_arrow(p.schema(), outs, 1)

        # pandas group-by with nulls as a group (dropna=False)
        df = {}
        for name, b, (d, v) in key_vals:
            s = pd.Series(list(d) if d.dtype == object else d)
            if v is not None:
                s = s.where(pd.Series(v), other=pd.NA)
            df[name] = s
        pdf = pd.DataFrame(df)
        grouped = pdf.groupby(list(df.keys()), dropna=False, sort=True)
        idx_groups = list(grouped.indices.items()) if len(df) > 1 else [
            (k, g) for k, g in grouped.indices.items()]
        # Build group rows deterministically
        group_keys = list(grouped.indices.keys())
        out_rows = len(group_keys)
        key_outs = []
        for ki, (name, b, (d, v)) in enumerate(key_vals):
            kd = np.empty(out_rows, dtype=d.dtype if d.dtype == object
                          else d.dtype)
            kv = np.ones(out_rows, dtype=bool)
            for gi, gk in enumerate(group_keys):
                first_idx = grouped.indices[gk][0]
                if v is not None and not v[first_idx]:
                    kv[gi] = False
                    kd[gi] = 0 if d.dtype != object else None
                else:
                    kd[gi] = d[first_idx]
            key_outs.append((kd, None if kv.all() else kv))
        agg_outs = []
        for name, b, child_vals in agg_specs:
            od = np.empty(out_rows, dtype=object) if b.dtype.is_nested \
                else np.zeros(out_rows, dtype=self._agg_np_dtype(b))
            ov = np.ones(out_rows, dtype=bool)
            for gi, gk in enumerate(group_keys):
                idx = grouped.indices[gk]
                val, ok = self._agg_one(b, child_vals, idx)
                od[gi] = val
                ov[gi] = ok
            agg_outs.append((od, None if ov.all() else ov))
        return values_to_arrow(p.schema(), key_outs + agg_outs, out_rows)

    @staticmethod
    def _agg_np_dtype(b):
        if b.dtype.is_nested:
            return object  # list payloads (collect_list / collect_set)
        return b.dtype.numpy_dtype

    @staticmethod
    def _agg_one(b, child_vals, idx):
        from .. import aggfns as A
        cd, cv = child_vals[0]
        if isinstance(b, A._BinaryAgg):
            # rows where EITHER side is null are excluded (Spark corr/covar)
            yd, yv = child_vals[1]
            both = np.ones(len(cd), dtype=bool)
            if cv is not None:
                both &= cv
            if yv is not None:
                both &= yv
            sel = idx[both[idx]]
            if len(sel) == 0:
                return 0, False

            def f64(d, e):
                d = d.astype(np.float64)
                if e.dtype.is_decimal:
                    d = d / 10 ** e.dtype.scale
                return d

            x = f64(cd, b.children[0])[sel]
            y = f64(yd, b.children[1])[sel]
            n_ = float(len(sel))
            cov = (x * y).sum() - x.sum() * y.sum() / n_
            if isinstance(b, A.Corr):
                if n_ < 2:  # NULL for <2 points (non-legacy Spark)
                    return 0, False
                vx = max((x * x).sum() - x.sum() ** 2 / n_, 0.0)
                vy = max((y * y).sum() - y.sum() ** 2 / n_, 0.0)
                den = np.sqrt(vx * vy)
                return (cov / den if den > 0 else np.nan), True
            if b.sample:
                if n_ < 2:  # NULL for n==1 (non-legacy Spark)
                    return 0, False
                return cov / (n_ - 1), True
            return cov / n_, True
        sel = idx if cv is None else idx[cv[idx]]
        if isinstance(b, A.CountStar):
            return len(idx), True
        if isinstance(b, A.Count):
            return len(sel), True
        if len(sel) == 0:
            return 0, False
        x = cd[sel]
        if isinstance(b, A.Sum):
            return x.sum(), True
        if isinstance(b, A.Min):
            return x.min(), True
        if isinstance(b, A.Max):
            return x.max(), True
        if isinstance(b, A.Average):
            src = b.children[0].dtype
            xf = x.astype(np.float64)
            if src.is_decimal:
                xf = xf / 10 ** src.scale
            return xf.mean(), True
        if isinstance(b, A._CentralMoment):
            src = b.children[0].dtype
            xf = x.astype(np.float64)
            if src.is_decimal:
                xf = xf / 10 ** src.scale
            n_ = float(len(xf))
            m2 = max((xf * xf).sum() - xf.sum() ** 2 / n_, 0.0)
            if b.sample:
                if n_ < 2:  # NULL for n==1 (non-legacy Spark)
                    return 0, False
                var = m2 / (n_ - 1)
            else:
                var = m2 / n_
            return (np.sqrt(var) if b.sqrt else var), True
        if isinstance(b, A.CollectList):
            src = b.children[0].dtype
            vals = cd[sel]
            if src.is_decimal:
                vals = vals.astype(np.float64) / 10 ** src.scale
            pyvals = list(vals) if not isinstance(vals, list) else vals
            if isinstance(b, A.CollectSet):
                seen = []
                for v in pyvals:
                    if v not in seen:
                        seen.append(v)
                pyvals = seen
            return [_py_scalar(v) for v in pyvals], True
        if isinstance(b, A.Percentile):
            src = b.children[0].dtype
            xf = x.astype(np.float64)
            if src.is_decimal:
                xf = xf / 10 ** src.scale
            return float(np.percentile(xf, b.q * 100.0,
                                       method="linear")), True
        if isinstance(b, A.Last):
            pick = idx if not b.ignore_nulls else sel
            i = pick[-1]
            return cd[i], (cv is None or cv[i])
        if isinstance(b, A.First):
            pick = idx if not b.ignore_nulls else sel
            i = pick[0]
            return cd[i], (cv is None or cv[i])
        raise NotImplementedError(type(b).__name__)

    def _agg_scalar(self, b, child_vals, n):
        idx = np.arange(n)
        val, ok = self._agg_one(b, child_vals, idx)
        if b.dtype.is_nested:
            out = np.empty(1, dtype=object)
            out[0] = val
        else:
            out = np.array([val], dtype=self._agg_np_dtype(b))
        return out, None if ok else np.array([False])

    def _run_sort(self, ctx, p: L.Sort):
        in_schema = self.children[0].output_schema
        return sort_table(
            self._child_table(ctx), in_schema,
            [(bind(o.expr, in_schema), o.ascending, o.nulls_first)
             for o in p.orders])

    @staticmethod
    def _sort_key(d, v, ascending, nulls_first):
        """Integer rank key: encodes value order, direction, null placement.

        Rank-based (not value-based) so int64 precision and NaN ordering
        (Spark: NaN sorts greater than any number) are exact.
        """
        n = len(d)
        null_mask = (~v) if v is not None else np.zeros(n, dtype=bool)
        key = np.empty(n, dtype=np.int64)
        # DENSE ranks: equal values MUST share a key — per-position ranks
        # would reverse tie order under descending negation, breaking the
        # stable minor->major composition of multi-key sorts
        if d.dtype == object:  # strings
            null_mask = null_mask | np.array([x is None for x in d], dtype=bool)
            non_null = [i for i in range(n) if not null_mask[i]]
            non_null.sort(key=lambda i: d[i])
            rank = -1
            prev = object()
            for i in non_null:
                if d[i] != prev:
                    rank += 1
                    prev = d[i]
                key[i] = rank
            if not ascending:
                key[~null_mask] = -key[~null_mask]
        else:
            order = np.argsort(d, kind="stable")  # NaN sorts last = greatest
            sv = d[order]
            diff = np.ones(n, dtype=bool)
            if n > 1:
                neq = sv[1:] != sv[:-1]
                if sv.dtype.kind == "f":  # equal NaNs are one rank group
                    both_nan = np.isnan(sv[1:]) & np.isnan(sv[:-1])
                    neq = neq & ~both_nan
                diff[1:] = neq
            rank = np.empty(n, dtype=np.int64)
            rank[order] = np.cumsum(diff) - 1
            key = rank if ascending else -rank
        key[null_mask] = (np.iinfo(np.int64).min if nulls_first
                          else np.iinfo(np.int64).max)
        return key

    def _run_window(self, ctx, p: "L.Window"):
        """Host window evaluation mirroring WindowExec semantics.

        Same sorted/segmented model as the device path (ops/window.py) with
        numpy primitives; output is in (partition, order) sorted order like
        the device operator and Spark's WindowExec.
        """
        import pandas as pd
        import pyarrow as pa
        from ..plan.planner import strip_alias
        from ..windowfns import WindowExpression
        in_schema = self.children[0].output_schema
        table = self._child_table(ctx)
        vals = arrow_to_values(table, in_schema)
        n = table.num_rows
        bound = [(name, strip_alias(bind(e, in_schema)))
                 for name, e in p.window_exprs]
        spec = bound[0][1].spec

        # ---- sort by (partition asc nulls-first, then order spec) ----
        perm = np.arange(n)
        orderings = ([(e, True, True) for e in spec.partition_by]
                     + [(o.expr, o.ascending, o.nulls_first)
                        for o in spec.order_by])
        for e, asc, nf in reversed(orderings):
            d, v = eval_cpu(e, vals, n)
            d2 = d[perm]
            v2 = v[perm] if v is not None else None
            keys = self._sort_key(d2, v2, asc, nf)
            perm = perm[np.argsort(keys, kind="stable")]

        def codes_for(exprs) -> np.ndarray:
            """Per-row group codes over sorted order (nulls/NaN = own code)."""
            if not exprs or n == 0:
                return np.zeros(n, dtype=np.int64)
            cols = []
            for e in exprs:
                d, v = eval_cpu(e, vals, n)
                s = pd.Series(list(d[perm]) if d.dtype == object else d[perm])
                if v is not None:
                    s = s.where(pd.Series(v[perm]), other=pd.NA)
                codes, _ = pd.factorize(s, use_na_sentinel=False)
                cols.append(codes)
            key = cols[0].astype(np.int64)
            for c in cols[1:]:
                key = key * (c.max() + 1 if len(c) else 1) + c
            return key

        seg_codes = codes_for(spec.partition_by)
        peer_codes = codes_for(spec.partition_by
                               + [o.expr for o in spec.order_by])
        arange = np.arange(n)
        seg_start = np.ones(n, dtype=bool)
        seg_start[1:] = seg_codes[1:] != seg_codes[:-1]
        peer_start = np.ones(n, dtype=bool)
        peer_start[1:] = peer_codes[1:] != peer_codes[:-1]
        seg_start_pos = np.maximum.accumulate(np.where(seg_start, arange, 0))
        peer_start_pos = np.maximum.accumulate(np.where(peer_start, arange, 0))
        seg_last = np.ones(n, dtype=bool)
        seg_last[:-1] = seg_start[1:]
        peer_last = np.ones(n, dtype=bool)
        peer_last[:-1] = peer_start[1:]
        big = n if n else 1
        seg_end_pos = np.minimum.accumulate(
            np.where(seg_last, arange, big)[::-1])[::-1]
        peer_end_pos = np.minimum.accumulate(
            np.where(peer_last, arange, big)[::-1])[::-1]
        seg_ids = np.cumsum(seg_start) - 1 if n else np.zeros(0, dtype=int)

        outs = []
        for name, w in bound:
            outs.append(self._window_one(
                w, vals, n, perm, dict(
                    arange=arange, seg_start=seg_start,
                    seg_start_pos=seg_start_pos, seg_end_pos=seg_end_pos,
                    peer_start=peer_start, peer_start_pos=peer_start_pos,
                    peer_end_pos=peer_end_pos, seg_ids=seg_ids)))

        sorted_tbl = table.take(pa.array(perm)) if n else table
        win_tbl = values_to_arrow(
            Schema([f for f in p.schema().fields[len(in_schema):]]), outs, n)
        for i, f in enumerate(win_tbl.schema):
            sorted_tbl = sorted_tbl.append_column(f, win_tbl.column(i))
        return sorted_tbl

    def _window_one(self, w, vals, n: int, perm, s) -> tuple:
        import pandas as pd
        from .. import aggfns as A
        from .. import windowfns as WF
        func = w.func
        frame = w.spec.frame
        if w.spec.order_by and frame.kind == "range" and not (
                frame.lo is None and frame.hi in (None, 0)):
            # bounded value-range frame: stash the sorted order key so
            # _frame_bounds can resolve per-row value windows
            o = w.spec.order_by[0]
            od, ov = eval_cpu(o.expr, vals, n)
            s = dict(s)
            s["order0"] = np.asarray(od)[perm]
            s["order0_valid"] = (None if ov is None
                                 else np.asarray(ov, bool)[perm])
            s["order0_asc"] = o.ascending
        arange, seg_ids = s["arange"], s["seg_ids"]
        ssp, sep = s["seg_start_pos"], s["seg_end_pos"]
        pep = s["peer_end_pos"]
        if isinstance(func, WF.RowNumber):
            return (arange - ssp + 1).astype(np.int32), None
        if isinstance(func, WF.Rank):
            return (s["peer_start_pos"] - ssp + 1).astype(np.int32), None
        if isinstance(func, WF.DenseRank):
            dc = np.cumsum(s["peer_start"])
            return (dc - dc[ssp] + 1).astype(np.int32), None
        if isinstance(func, WF.PercentRank):
            size1 = (sep - ssp).astype(np.float64)
            r = (s["peer_start_pos"] - ssp).astype(np.float64)
            return np.where(size1 > 0, r / np.maximum(size1, 1), 0.0), None
        if isinstance(func, WF.CumeDist):
            size = (sep - ssp + 1).astype(np.float64)
            return (pep - ssp + 1).astype(np.float64) / size, None
        if isinstance(func, WF.NTile):
            size = sep - ssp + 1
            rn0 = arange - ssp
            nt = func.n
            base, rem = size // nt, size % nt
            bigsz = base + 1
            in_big = rn0 < bigsz * rem
            tile = np.where(in_big, rn0 // np.maximum(bigsz, 1),
                            rem + (rn0 - bigsz * rem) // np.maximum(base, 1))
            return (tile + 1).astype(np.int32), None
        if isinstance(func, WF.Lag):  # Lead subclasses Lag
            d, v = eval_cpu(func.children[0], vals, n)
            d, v = d[perm], (v[perm] if v is not None else None)
            off = func.offset_sign * func.offset
            src = arange - off
            in_seg = (src >= ssp) & (src <= sep)
            safe = np.clip(src, 0, max(n - 1, 0))
            out = d[safe]
            valid = in_seg if v is None else (in_seg & v[safe])
            if len(func.children) > 1:
                dd, dv = eval_cpu(func.children[1], vals, n)
                # permute the default into sorted order too (output rows
                # are in window-sorted order)
                dd = dd[perm]
                dv = dv[perm] if dv is not None else None
                out = np.where(in_seg, out, dd.astype(out.dtype)
                               if out.dtype != object else dd)
                valid = np.where(in_seg, valid,
                                 np.ones(n, bool) if dv is None else dv)
            return out, (None if valid.all() else valid)
        assert isinstance(func, A.AggregateExpression), func
        fname = func.func
        if fname == "count(*)":
            m = np.ones(n, dtype=bool)
            return self._framed_sum_np(frame, m.astype(np.int64), s), None
        d, v = eval_cpu(func.children[0], vals, n)
        d, v = d[perm], (v[perm] if v is not None else None)
        m = np.ones(n, dtype=bool) if v is None else v.copy()
        if fname == "count":
            return self._framed_sum_np(frame, m.astype(np.int64), s), None
        cnt = self._framed_sum_np(frame, m.astype(np.int64), s)
        ok = cnt > 0
        if fname in ("sum", "avg"):
            src_dt = func.children[0].dtype
            if fname == "avg" or src_dt.is_floating:
                data = d.astype(np.float64)
                if src_dt.is_decimal:
                    data = data / 10.0 ** src_dt.scale
            else:
                data = d.astype(np.int64)
            contrib = np.where(m, data, 0)
            tot = self._framed_sum_np(frame, contrib, s)
            if fname == "avg":
                return tot / np.maximum(cnt, 1), (None if ok.all() else ok)
            return (tot.astype(func.dtype.numpy_dtype),
                    None if ok.all() else ok)
        if fname in ("min", "max"):
            if not (frame.is_unbounded_both or frame.is_running):
                return self._bounded_frame_minmax(fname, frame, d, m, s, ok,
                                                  func.dtype.numpy_dtype)
            # int64/decimal stay in the integer domain (pandas nullable
            # Int64): a float64 detour corrupts values beyond 2^53
            integral = d.dtype.kind in "iu"
            if integral:
                ser = pd.Series(d, dtype="Int64")
                ser = ser.where(pd.Series(m))
            else:
                ser = pd.Series(d.astype(np.float64)
                                if d.dtype != object else d)
                ser = ser.where(pd.Series(m), other=np.nan)
            g = ser.groupby(seg_ids)
            if frame.is_unbounded_both:
                r = g.transform("min" if fname == "min" else "max")
            else:
                r = g.cummin() if fname == "min" else g.cummax()
                r = r.iloc[pep].reset_index(drop=True) \
                    if frame.kind == "range" else r
            if integral:
                vals = r.fillna(0).to_numpy(dtype=np.int64)
            else:
                vals = np.nan_to_num(r.to_numpy())
            out = np.where(ok, vals, 0).astype(func.dtype.numpy_dtype)
            return out, (None if ok.all() else ok)
        if fname in ("first", "last"):
            ignore = getattr(func, "ignore_nulls", False)
            lo_pos, hi_pos = self._frame_bounds(frame, s)
            out = np.zeros(n, dtype=d.dtype if d.dtype != object else object)
            okv = np.zeros(n, dtype=bool)
            for i in range(n):
                a, b = int(lo_pos[i]), int(hi_pos[i])
                if b < a:
                    continue
                if ignore:
                    rng = range(a, b + 1) if fname == "first" \
                        else range(b, a - 1, -1)
                    for j in rng:
                        if m[j]:
                            out[i] = d[j]
                            okv[i] = True
                            break
                else:
                    j = a if fname == "first" else b
                    out[i] = d[j]
                    okv[i] = bool(v is None or v[j])
            return out, (None if okv.all() else okv)
        raise NotImplementedError(f"CPU window aggregate {fname}")

    @staticmethod
    def _frame_bounds(frame, s):
        """Per-row inclusive [lo_pos, hi_pos] frame bounds in sorted order."""
        arange, ssp, sep = s["arange"], s["seg_start_pos"], s["seg_end_pos"]
        if frame.kind == "range":
            if frame.lo is None and frame.hi in (None, 0):
                lo_pos = ssp
                hi_pos = sep if frame.hi is None else s["peer_end_pos"]
                return lo_pos, hi_pos
            # bounded value-range: per-row scan within the partition
            # (brute force; this is the declared CPU fallback regime).
            # Offsets apply in ORDER direction (Spark): for a descending
            # key "preceding" means larger values.
            key = s["order0"]
            kv = s.get("order0_valid")
            sgn = 1 if s["order0_asc"] else -1
            n = len(key)
            lo_pos = np.empty(n, dtype=np.int64)
            hi_pos = np.empty(n, dtype=np.int64)
            for i in range(n):
                a, b = int(ssp[i]), int(sep[i])
                if kv is not None and not kv[i]:
                    # null order key: the frame is the null peer group
                    js = [j for j in range(a, b + 1)
                          if kv is not None and not kv[j]]
                else:
                    js = []
                    for j in range(a, b + 1):
                        if kv is not None and not kv[j]:
                            continue
                        delta = (key[j] - key[i]) * sgn
                        if (frame.lo is None or delta >= frame.lo) and \
                                (frame.hi is None or delta <= frame.hi):
                            js.append(j)
                if js:
                    lo_pos[i], hi_pos[i] = js[0], js[-1]
                else:
                    lo_pos[i], hi_pos[i] = 1, 0  # empty
            return lo_pos, hi_pos
        lo_pos = ssp if frame.lo is None else np.maximum(
            arange + frame.lo, ssp)
        hi_pos = sep if frame.hi is None else np.minimum(
            arange + frame.hi, sep)
        return lo_pos, hi_pos

    def _bounded_frame_minmax(self, fname, frame, d, m, s, ok, np_dt):
        """Brute-force sliding min/max (the frames the device declines)."""
        n = len(d)
        lo_pos, hi_pos = self._frame_bounds(frame, s)
        out = np.zeros(n, dtype=np_dt)
        for i in range(n):
            vals = [d[j] for j in range(int(lo_pos[i]), int(hi_pos[i]) + 1)
                    if m[j]]
            if vals:
                out[i] = min(vals) if fname == "min" else max(vals)
        return out, (None if ok.all() else ok)

    @staticmethod
    def _framed_sum_np(frame, contrib: np.ndarray, s) -> np.ndarray:
        n = len(contrib)
        arange, ssp, sep = s["arange"], s["seg_start_pos"], s["seg_end_pos"]
        if n == 0:
            return contrib
        c = np.cumsum(contrib)
        if frame.lo is None and frame.hi is None:
            tot = c[sep] - c[ssp] + contrib[ssp]
            return tot
        if frame.lo is None and frame.hi == 0:
            run = c - (c[ssp] - contrib[ssp])
            if frame.kind == "range":
                run = run[s["peer_end_pos"]]
            return run
        lo_pos, hi_pos = CpuOpExec._frame_bounds(frame, s)
        empty = hi_pos < lo_pos
        lo_c = np.clip(lo_pos, 0, n - 1)
        hi_c = np.clip(hi_pos, 0, n - 1)
        out = c[hi_c] - c[lo_c] + contrib[lo_c]
        return np.where(empty, 0, out)

    def _run_join(self, ctx, p: L.Join):
        """SQL-semantics host join (GpuHashJoin CPU twin).

        Matches are computed as (left-row, right-row) index pairs over the
        inner equi-join, with the residual condition applied to the *pairs*
        (outer-join conditions affect matching, not post-filtering); outer
        rows are then null-padded from the unmatched index sets.  pandas
        merge alone is wrong twice over: it matches NA keys to each other
        and cannot express per-pair residual conditions.
        """
        import pandas as pd
        import pyarrow as pa
        lt = self._child_table(ctx, 0)
        rt = self._child_table(ctx, 1)
        how = {"left_outer": "left", "right_outer": "right",
               "full_outer": "full", "left_semi": "semi",
               "left_anti": "anti"}.get(p.how, p.how)
        using = getattr(p, "using", None)
        lpd, rpd = lt.to_pandas(), rt.to_pandas()
        lpd = lpd.reset_index(drop=True)
        rpd = rpd.reset_index(drop=True)

        if how == "cross":
            li = np.repeat(np.arange(len(lpd)), len(rpd))
            ri = np.tile(np.arange(len(rpd)), len(lpd))
        elif using:
            lk = lpd[using].copy()
            rk = rpd[using].copy()
            lk["__li"] = np.arange(len(lpd))
            rk["__ri"] = np.arange(len(rpd))
            # SQL: null keys never match
            lk = lk.dropna(subset=using)
            rk = rk.dropna(subset=using)
            pairs = lk.merge(rk, on=using, how="inner")
            li = pairs["__li"].to_numpy()
            ri = pairs["__ri"].to_numpy()
        else:
            # pair-keyed join (distinct key names on each side)
            lnames = [getattr(k, "name", None) for k in p.left_keys]
            rnames = [getattr(k, "name", None) for k in p.right_keys]
            if not all(lnames) or not all(rnames):
                raise NotImplementedError(
                    "CPU join requires bare column join keys")
            lk = lpd[lnames].copy()
            rk = rpd[rnames].copy()
            lk["__li"] = np.arange(len(lpd))
            rk["__ri"] = np.arange(len(rpd))
            lk = lk.dropna(subset=lnames)
            rk = rk.dropna(subset=rnames)
            pairs = lk.merge(rk, left_on=lnames, right_on=rnames,
                             how="inner")
            li = pairs["__li"].to_numpy()
            ri = pairs["__ri"].to_numpy()

        if p.condition is not None and len(li):
            joined = pd.concat(
                [lpd.iloc[li].reset_index(drop=True),
                 rpd.drop(columns=using or []).iloc[ri].reset_index(drop=True)],
                axis=1)
            jt = pa.Table.from_pandas(joined, preserve_index=False)
            pair_schema = self._join_pair_schema(p)
            vals = arrow_to_values(jt, pair_schema)
            d, v = eval_cpu(bind(p.condition, pair_schema), vals, len(joined))
            keep = d if v is None else (d & v)
            li, ri = li[keep], ri[keep]

        if how in ("inner", "cross"):
            return self._join_emit(p, lpd, rpd, using, li, ri, [], [])
        if how == "semi":
            sel = np.zeros(len(lpd), dtype=bool)
            sel[li] = True
            return pa.Table.from_pandas(lpd[sel], preserve_index=False)
        if how == "existence":
            ex = np.zeros(len(lpd), dtype=bool)
            ex[li] = True
            out = lpd.copy()
            out[p.schema().names()[-1]] = ex
            return pa.Table.from_pandas(out, preserve_index=False)
        if how == "anti":
            sel = np.ones(len(lpd), dtype=bool)
            sel[li] = False
            return pa.Table.from_pandas(lpd[sel], preserve_index=False)
        l_unmatched = np.setdiff1d(np.arange(len(lpd)), li) \
            if how in ("left", "full") else np.array([], dtype=int)
        r_unmatched = np.setdiff1d(np.arange(len(rpd)), ri) \
            if how in ("right", "full") else np.array([], dtype=int)
        return self._join_emit(p, lpd, rpd, using, li, ri,
                               l_unmatched, r_unmatched)

    def _join_pair_schema(self, p: L.Join) -> Schema:
        """Schema of matched pairs (left ++ right-minus-using), all columns
        as in the inner join, for residual condition binding."""
        from ..batch import Field
        l, r = p.children[0].schema(), p.children[1].schema()
        using = set(getattr(p, "using", []) or [])
        return Schema(list(l.fields)
                      + [f for f in r.fields if f.name not in using])

    def _join_emit(self, p, lpd, rpd, using, li, ri, l_un, r_un):
        import pandas as pd
        import pyarrow as pa
        using = using or []
        rcols = [c for c in rpd.columns if c not in using]
        parts = []
        core = pd.concat(
            [lpd.iloc[li].reset_index(drop=True),
             rpd[rcols].iloc[ri].reset_index(drop=True)], axis=1)
        parts.append(core)
        if len(l_un):
            lu = lpd.iloc[l_un].reset_index(drop=True)
            for c in rcols:
                lu[c] = pd.Series([None] * len(lu), dtype=object)
            parts.append(lu)
        if len(r_un):
            ru = rpd.iloc[r_un].reset_index(drop=True)
            out = pd.DataFrame()
            for c in lpd.columns:
                # USING keys surface from the right side (coalesce semantics)
                out[c] = ru[c] if c in using else pd.Series(
                    [None] * len(ru), dtype=object)
            for c in rcols:
                out[c] = ru[c]
            parts.append(out)
        merged = pd.concat(parts, ignore_index=True) if len(parts) > 1 \
            else parts[0]
        arrays = []
        from ..batch import logical_to_arrow
        for f in p.schema():
            s = merged[f.name]
            # pandas null-padding upcasts int columns to float (values like
            # 3 -> 3.0, nulls -> NaN); undo that per the TARGET dtype: NaN
            # is a legitimate value only in float columns, and int-valued
            # floats cast back so pa.array(type=int64) accepts them
            try:
                kind = np.dtype(f.dtype.numpy_dtype).kind
            except (AttributeError, TypeError):  # nested/host-carried
                kind = "O"

            def conv(x):
                if x is None:
                    return None
                if isinstance(x, (float, np.floating)):
                    if x != x:  # NaN
                        return float(x) if kind == "f" else None
                    if kind in "iu":
                        if abs(x) > 2**53:
                            raise ValueError(
                                f"int column round-tripped through float64 "
                                f"lost precision: {x!r}")
                        return int(x)
                    return float(x)
                if isinstance(x, (str, bytes, list, dict, np.ndarray)):
                    return list(x) if isinstance(x, np.ndarray) else x
                if pd.isna(x):
                    return None
                return x
            arrays.append(pa.array([conv(x) for x in s],
                                   type=logical_to_arrow(f.dtype)))
        return pa.table(dict(zip(p.schema().names(), arrays)))

"""WindowExec: the device window operator.

Analog of GpuWindowExec.scala (batched :1329 / running :1655 / double-pass
:2004) re-designed for XLA: instead of dispatching one cuDF aggregation per
window expression, ALL window expressions sharing a (partition, order) spec
compile into ONE fused program — sort once, build the segment structure once,
then every function is a segmented scan/reduce over it (ops/window.py).

Output rows are emitted in (partition, order) sorted order, which is the
order Spark's WindowExec produces (it requires sorted input and preserves
it).

String partition and order keys enter the program as int32 dictionary
codes (ops/strings.key_view): equal strings have equal codes, which is all
a partition needs, and an ORDER BY key gets the rank of each string among
the column's distinct values.  The strings themselves ride through the
gather as the columns they were (a ``DictStringColumn`` stays on the device).
"""

from __future__ import annotations

from typing import Iterator, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..batch import ColumnBatch, DeviceColumn, Field, HostStringColumn, Schema
from ..exprs import EvalContext
from ..ops import batch_utils
from ..ops.strings import key_view
from ..ops.window import SortedWindowContext
from ..utils.metrics import counted_span
from ..windowfns import WindowExpression
from .physical import ExecContext, TpuExec, _cached_program, program

__all__ = ["WindowExec"]


class WindowExec(TpuExec):
    def __init__(self, child: TpuExec,
                 window_exprs: List[Tuple[str, WindowExpression]]):
        super().__init__([child])
        self.window_exprs = window_exprs
        fields = list(child.output_schema.fields)
        for name, e in window_exprs:
            fields.append(Field(name, e.dtype, e.nullable))
        self._schema = Schema(fields)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def node_desc(self):
        spec = self.window_exprs[0][1].spec
        np_, no_ = len(spec.partition_by), len(spec.order_by)
        return (f"TpuWindow [{', '.join(n for n, _ in self.window_exprs)}] "
                f"part={np_} order={no_}")

    def child_coalesce_goal(self, i, conf):
        # windows evaluate over the whole (sorted) input at once
        from .coalesce import RequireSingleBatch
        return RequireSingleBatch

    def _fingerprint(self) -> str:
        return "|".join(e.fingerprint() for _, e in self.window_exprs)

    def _string_key_ordinals(self):
        """Ordinals of the string columns the spec's keys read: they enter
        the program as int32 dictionary codes (ops/strings.key_view),
        order-preserving ones where an ORDER BY key reads the column."""
        from .planner import string_key_ordinals
        spec = self.window_exprs[0][1].spec
        ordered = string_key_ordinals([o.expr for o in spec.order_by])
        equal = [o for o in string_key_ordinals(spec.partition_by)
                 if o not in ordered]
        return equal, ordered

    def _build_fn(self):
        wexprs = [e for _, e in self.window_exprs]
        spec = wexprs[0].spec

        def fn(arrays, num_rows):
            cap = next(a[0].shape[0] for a in arrays if a is not None)
            active = jnp.arange(cap, dtype=jnp.int32) < num_rows
            ectx = EvalContext(list(arrays), cap, active=active)
            part_keys = [e.eval(ectx) for e in spec.partition_by]
            order_keys = [o.expr.eval(ectx) for o in spec.order_by]
            w = SortedWindowContext(
                part_keys, order_keys,
                [not o.ascending for o in spec.order_by],
                [o.nulls_first for o in spec.order_by], active)
            with jax.named_scope("window_scans"):
                outs = tuple(we.window_eval(w, ectx) for we in wexprs)
            return w.perm, outs

        return fn

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        m = ctx.metric_set(self.op_id)
        batches = list(self.children[0].execute(ctx))
        if not batches:
            return
        with counted_span("window_exec_s", self.op_id, "window:exec",
                          "window") as stats:
            result = self._run(ctx, m, batches, stats)
        m.add("numOutputRows", result.num_rows)
        m.add("numOutputBatches", 1)
        yield result

    def _run(self, ctx, m, batches, stats) -> ColumnBatch:
        whole = batch_utils.compact(batch_utils.concat_batches(batches)) \
            if len(batches) > 1 else batch_utils.compact(batches[0])
        stats.window_rows += whole.num_rows
        equal, ordered = self._string_key_ordinals()
        with m.time("opTime"):
            fn = _cached_program("window|" + self._fingerprint(),
                                 lambda: program("window", self._build_fn()))

            def run(b: ColumnBatch):
                keyed = key_view(key_view(b, equal, False, ctx.device),
                                 ordered, True, ctx.device)
                arrays = tuple(
                    (c.data, c.valid) if isinstance(c, DeviceColumn) else None
                    for c in keyed.columns)
                return b, fn(arrays, np.int32(b.num_rows))

            # retry protocol like sort/agg, but split=None: a window frame
            # may span any row range, so halving the input would change
            # results — spill+retry only (GpuWindowExec is likewise
            # withRetryNoSplit).  run returns the (possibly re-materialized)
            # batch so gather uses the same buffers the kernel saw.
            from ..memory.retry import with_retry
            (whole, (perm, outs)), = with_retry(ctx, whole, run, split=None)
            out = batch_utils.gather(whole, perm, whole.num_rows)
            cols = list(out.columns)
            for (name, we), (d, v) in zip(self.window_exprs, outs):
                cols.append(DeviceColumn(
                    we.dtype, d.astype(we.dtype.numpy_dtype), v))
        return ColumnBatch(self._schema, cols, whole.num_rows)

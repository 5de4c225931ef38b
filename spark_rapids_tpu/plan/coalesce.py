"""Batch coalescing goals: concat small batches before expensive operators.

Reference: the CoalesceGoal algebra (GpuCoalesceBatches.scala:159-192 —
``TargetSize``/``RequireSingleBatch`` with max-combining) and the
GpuCoalesceBatches exec that GpuTransitionOverrides inserts in front of
operators that pay per-batch overhead.  TPU shape: per-batch cost here is a
full dispatch plus an XLA program per capacity bucket, so stitching many
small scan/fallback batches into ``batchSizeRows``-sized ones amortizes both.  Consumers DECLARE goals
(`TpuExec.child_coalesce_goal`); the transition pass (`insert_coalesce`)
materializes them as CoalesceBatchesExec nodes, skipping partition-aligned
children whose batch boundaries are semantic (the shuffled-join zip).
"""

from __future__ import annotations

from typing import Iterator, Optional

from ..batch import ColumnBatch, Schema
from ..ops import batch_utils
from .physical import ExecContext, TpuExec

__all__ = ["CoalesceGoal", "TargetSize", "RequireSingleBatch", "max_goal",
           "CoalesceBatchesExec", "insert_coalesce"]


class CoalesceGoal:
    """Desired batch granularity for a consumer's input stream."""

    def satisfied_by(self, num_rows: int, is_only: bool) -> bool:
        raise NotImplementedError


class TargetSize(CoalesceGoal):
    """Batches of roughly ``rows`` rows: merge smaller, pass larger."""

    def __init__(self, rows: int):
        self.rows = int(rows)

    def satisfied_by(self, num_rows, is_only):
        return num_rows >= self.rows

    def __repr__(self):
        return f"TargetSize({self.rows})"

    def __eq__(self, other):
        return isinstance(other, TargetSize) and other.rows == self.rows


class _RequireSingleBatch(CoalesceGoal):
    """The whole stream in ONE batch (window/global-sort style consumers)."""

    def satisfied_by(self, num_rows, is_only):
        return is_only

    def __repr__(self):
        return "RequireSingleBatch"


RequireSingleBatch = _RequireSingleBatch()


def max_goal(a: Optional[CoalesceGoal], b: Optional[CoalesceGoal]
             ) -> Optional[CoalesceGoal]:
    """Combine goals: the stricter wins (GpuCoalesceBatches.scala maxSize
    semantics — RequireSingleBatch dominates any TargetSize)."""
    if a is None:
        return b
    if b is None:
        return a
    if isinstance(a, _RequireSingleBatch) or isinstance(b, _RequireSingleBatch):
        return RequireSingleBatch
    return a if a.rows >= b.rows else b


class CoalesceBatchesExec(TpuExec):
    """Concatenates child batches up to a goal (GpuCoalesceBatches analog).

    TargetSize: accumulate until >= rows, emit, repeat; an already-large
    batch passes through untouched.  RequireSingleBatch: concat everything.
    Empty input yields nothing (sources own empty-result semantics).
    """

    region_fusible = True

    def __init__(self, child: TpuExec, goal: CoalesceGoal):
        super().__init__([child])
        self.goal = goal

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def node_desc(self):
        return f"TpuCoalesceBatches {self.goal!r}"

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        import jax
        import jax.numpy as jnp
        m = ctx.metric_set(self.op_id)
        # Per-batch live counts stay DEVICE scalars until a "look":
        # a host sync stalls the dispatch front until the device
        # drains, so masked batches must never block one each (the
        # pre-round-4 behavior).  A look resolves ALL outstanding counts
        # in one fetch; looks trigger on accumulated CAPACITY with a
        # doubling threshold, so a 1%-selective filter stream pays
        # O(log n_batches) fetches yet still merges to the goal by true
        # live count.
        goal_rows = getattr(self.goal, "rows", None)
        pending = []   # accumulated batches
        lives = []     # parallel: int when known, device scalar when not
        state = {"known": 0, "unknown_cap": 0, "cap_seen": 0,
                 "look_at": (2 * goal_rows) if goal_rows else float("inf")}

        def resolve():
            idx = [i for i, v in enumerate(lives)
                   if not isinstance(v, int)]
            if idx:
                # region-batched when fused (rides the prologue with any
                # staged stats); plain one-batched-fetch look otherwise
                from ..utils.metrics import region_fetch
                vals = region_fetch([lives[i] for i in idx])
                for i, v in zip(idx, vals):
                    lives[i] = int(v)
            state["known"] = sum(lives)
            state["unknown_cap"] = 0

        def flush():
            with m.time("opTime"):
                resolve()
                total = state["known"]
                if total == 0:
                    out = None
                elif len(pending) == 1 and pending[0].sel is None:
                    out = pending[0]
                else:
                    # merge through compact()'s capacity-bucketed
                    # sort+gather programs: a sortless slice-concat would
                    # need one XLA program per (n1, n2, ...) combination —
                    # a compile storm on remote backends
                    out = batch_utils.compact(
                        batch_utils.concat_batches(pending), n_live=total)
            if out is not None:
                m.add("numOutputRows", out.num_rows)
                m.add("numOutputBatches", 1)
            pending.clear()
            lives.clear()
            state.update(known=0, unknown_cap=0, cap_seen=0,
                         look_at=(2 * goal_rows) if goal_rows
                         else float("inf"))
            return out

        for b in self.children[0].execute(ctx):
            m.add("numInputBatches", 1)
            if b.num_rows == 0:
                continue
            if b.sel is None and self.goal.satisfied_by(b.num_rows, False):
                # dense and already at goal: pass through untouched — but
                # first flush anything smaller waiting ahead of it, so the
                # big batch never pays a merge sort for a few stray rows
                if pending:
                    out = flush()
                    if out is not None:
                        yield out
                m.add("numOutputRows", b.num_rows)
                m.add("numOutputBatches", 1)
                yield b
                continue
            pending.append(b)
            state["cap_seen"] += b.num_rows
            if b.sel is None:
                lives.append(b.num_rows)
                state["known"] += b.num_rows
            else:
                lives.append(jnp.sum(b.active_mask().astype(jnp.int32)))
                state["unknown_cap"] += b.num_rows
            if state["unknown_cap"] and state["cap_seen"] >= state["look_at"]:
                resolve()
                state["look_at"] = 2 * state["cap_seen"]
            if state["unknown_cap"] == 0 and \
                    self.goal.satisfied_by(state["known"], False):
                out = flush()
                if out is not None:
                    yield out
        if pending:
            out = flush()
            if out is not None:
                yield out


def insert_coalesce(phys: TpuExec, conf) -> TpuExec:
    """Transition pass: materialize declared consumer goals as
    CoalesceBatchesExec nodes (GpuTransitionOverrides.scala:50 model).

    Never inserted above a partition-aligned producer — those batch
    boundaries carry meaning (one batch per partition id) that a concat
    would destroy.
    """
    if not conf["spark.rapids.tpu.sql.coalesce.enabled"]:
        return phys
    byte_cap = conf["spark.rapids.tpu.sql.batchSizeBytes"]
    for i, child in enumerate(list(phys.children)):
        new_child = insert_coalesce(child, conf)
        goal = phys.child_coalesce_goal(i, conf)
        if isinstance(goal, TargetSize) and byte_cap > 0:
            # batchSizeBytes is the byte-denominated soft cap on a device
            # batch (the reference's ~1GiB target): clamp the row goal by
            # the schema's estimated row width
            from ..batch import estimated_row_bytes
            width = estimated_row_bytes(new_child.output_schema)
            goal = TargetSize(max(1, min(goal.rows, byte_cap // width)))
        if goal is not None and not new_child.outputs_partitions:
            if isinstance(new_child, CoalesceBatchesExec):
                # stacked demands combine instead of stacking nodes
                new_child.goal = max_goal(new_child.goal, goal)
            else:
                new_child = CoalesceBatchesExec(new_child, goal)
        phys.children[i] = new_child
    return phys

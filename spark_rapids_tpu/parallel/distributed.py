"""Distributed execution of planned queries over a jax.sharding.Mesh.

The reference distributes via Spark tasks + the UCX shuffle
(RapidsShuffleInternalManagerBase.scala); the TPU-native shape is SPMD: the
*same* partial-aggregate expression programs the single-chip planner builds
(plan/overrides.py → AggregateExec) run per device shard under ``shard_map``,
the shuffle is ONE ``lax.all_to_all`` over ICI (parallel/exchange.py), and
each device finalizes its hash range.  One jitted step = scan + fused
filter/project stage + partial aggregate + shuffle + final aggregate for the
whole mesh.

This is what the multi-chip dryrun drives: a DataFrame query is planned
normally, the planner's partial→exchange→final aggregate tree is recognized
(with an optional fused StageExec between scan and partial), and its bound
expressions are lowered into the SPMD step — the planner path and the
distributed path share one expression compiler.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

__all__ = ["plan_distributed_agg", "distributed_agg_collect"]


def _unwrap_region(node):
    """See through FusedRegionExec: the region wrapper groups execution,
    the member subtree below it is the real plan shape."""
    from ..plan.fusion import FusedRegionExec
    while isinstance(node, FusedRegionExec):
        node = node.children[0]
    return node


def _find_agg_tree(phys):
    """Locate final-agg → exchange → partial-agg in a planned query."""
    from ..plan.exchange_exec import ShuffleExchangeExec
    from ..plan.physical import AggregateExec
    node = _unwrap_region(phys)
    while node is not None:
        if isinstance(node, AggregateExec) and node.mode == "final":
            exch = _unwrap_region(node.children[0])
            if isinstance(exch, ShuffleExchangeExec):
                partial = _unwrap_region(exch.children[0])
                if isinstance(partial, AggregateExec) \
                        and partial.mode == "partial":
                    return node, exch, partial
        node = _unwrap_region(node.children[0]) if node.children else None
    raise ValueError(
        "plan has no partial->exchange->final aggregate "
        "(is spark.rapids.tpu.sql.exchange.enabled on?)")


def plan_distributed_agg(df, mesh, axis_name: str = "data",
                         bucket_cap: Optional[int] = None):
    """Compile a grouped-aggregate DataFrame query into one SPMD step.

    Returns (step_fn, feed, (final, partial, ops)).  ``step_fn(*cols)`` is
    the jitted shard_map program; ``feed(table)`` shards a host table's
    columns (data AND validity) across the mesh.  An optional fused
    filter/project StageExec between the scan and the partial aggregate is
    lowered into the step; any other operator in between is rejected rather
    than silently ignored.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..exprs import EvalContext
    from ..plan.overrides import apply_overrides
    from ..plan.physical import ScanExec, StageExec
    from .exchange import exchange_grouped_agg

    from ..plan.coalesce import CoalesceBatchesExec

    conf = df.session._tpu_conf()
    phys = apply_overrides(df._plan, conf)
    final, exch, partial = _find_agg_tree(phys)
    below = partial.children[0]
    # batch-granularity nodes are meaningless under shard_map (each shard
    # is one resident array, not a batch stream) — skip them
    while isinstance(below, CoalesceBatchesExec):
        below = below.children[0]
    stage = None
    if isinstance(below, StageExec):
        stage = below
        below = below.children[0]
        while isinstance(below, CoalesceBatchesExec):
            below = below.children[0]
    if not isinstance(below, ScanExec):
        raise ValueError(
            f"distributed lowering supports scan [+ fused stage] below the "
            f"partial aggregate, found {type(below).__name__}")
    in_schema = below.output_schema
    stage_fn = stage._build_fn(in_schema) if stage is not None else None
    ops = partial._buffer_ops()
    n_devices = int(np.prod(mesh.devices.shape))
    n_cols = len(in_schema)

    def step(*cols):
        cap = cols[0].shape[0]
        num_rows = cols[-1]
        data = cols[:n_cols]
        valid = cols[n_cols:2 * n_cols]
        active = jnp.arange(cap, dtype=jnp.int32) < num_rows
        arrays = [(d, v) for d, v in zip(data, valid)]
        if stage_fn is not None:
            out_arrays, active = stage_fn(tuple(arrays), None, num_rows)
            arrays = list(out_arrays)
        ectx = EvalContext(arrays, cap, active=active)
        keys = [e.eval(ectx) for _, e in partial.group_exprs]
        contribs = partial._update_contributions(ectx)
        bc = bucket_cap if bucket_cap is not None else cap
        fk, fv, fmask, overflow = exchange_grouped_agg(
            axis_name, n_devices, bc, keys,
            list(zip(contribs, ops)), active)
        outs = [d for d, _ in fk] + \
               [jnp.ones_like(fmask) if v is None else v for _, v in fk] + \
               [d for d, _ in fv] + \
               [jnp.ones_like(fmask) if v is None else v for _, v in fv]
        return tuple(outs) + (fmask, overflow.reshape(1))

    spec_in = tuple(P(axis_name) for _ in range(2 * n_cols + 1))
    n_out = 2 * len(partial.group_exprs) + 2 * len(ops) + 2
    spec_out = tuple(P(axis_name) for _ in range(n_out))
    from ..plan.physical import program
    step_fn = program("ici_agg_step",
                      jax.shard_map(step, mesh=mesh, in_specs=spec_in,
                                    out_specs=spec_out))

    def feed(table, rows_per_device: Optional[int] = None):
        """Shard a host table row-wise across the mesh (pad per device).
        Data and validity masks both ride; truncation is an error."""
        import jax.numpy as jnp
        from ..cpu.exec import arrow_to_values
        vals = arrow_to_values(table, in_schema)
        n = table.num_rows
        per_dev = rows_per_device or max(1, -(-n // n_devices))
        if per_dev * n_devices < n:
            raise ValueError(
                f"rows_per_device={per_dev} cannot hold {n} rows on "
                f"{n_devices} devices")
        data_cols, valid_cols = [], []
        for (d, v) in vals:
            pad = np.zeros(per_dev * n_devices, dtype=d.dtype)
            pad[:n] = d
            data_cols.append(jnp.asarray(pad))
            vp = np.zeros(per_dev * n_devices, dtype=bool)
            vp[:n] = True if v is None else v
            valid_cols.append(jnp.asarray(vp))
        counts = np.full(n_devices, per_dev, dtype=np.int32)
        full, rem = divmod(n, per_dev)
        counts[full + (1 if rem else 0):] = 0
        if rem:
            counts[full] = rem
        return tuple(data_cols) + tuple(valid_cols) + (jnp.asarray(counts),)

    return step_fn, feed, (final, partial, ops)


def distributed_agg_collect(df, mesh, table, axis_name: str = "data",
                            bucket_cap: Optional[int] = None):
    """Run the SPMD step and finalize to host rows (driver-side collect)."""
    import jax.numpy as jnp

    step_fn, feed, (final, partial, ops) = plan_distributed_agg(
        df, mesh, axis_name, bucket_cap)
    args = feed(table)
    outs = step_fn(*args)
    overflow = int(np.sum(np.asarray(outs[-1])))
    if overflow:
        raise RuntimeError(f"exchange bucket overflow: {overflow} rows")
    sel = np.asarray(outs[-2]).astype(bool)
    nk = len(partial.group_exprs)
    nb = len(ops)
    # hoist the selection once; everything below is per-group host work
    key_data = [np.asarray(outs[i])[sel] for i in range(nk)]
    key_valid = [np.asarray(outs[nk + i])[sel] for i in range(nk)]
    buf_data = [np.asarray(outs[2 * nk + i])[sel] for i in range(nb)]
    buf_valid = [np.asarray(outs[2 * nk + nb + i])[sel] for i in range(nb)]
    # finalize per aggregate with the planner's own finalize exprs
    fin_cols = []
    i = 0
    for name, agg in partial.agg_exprs:
        n_bufs = len(agg.buffers())
        vals = [(jnp.asarray(buf_data[i + k]), jnp.asarray(buf_valid[i + k]))
                for k in range(n_bufs)]
        d, v = agg.finalize(vals)
        fin_cols.append((np.asarray(d), None if v is None else np.asarray(v)))
        i += n_bufs
    rows: List[Tuple] = []
    for r in range(int(sel.sum())):
        row = []
        for kd, kv in zip(key_data, key_valid):
            row.append(kd[r].item() if kv[r] else None)
        for d, v in fin_cols:
            row.append(None if (v is not None and not v[r]) else d[r].item())
        rows.append(tuple(row))
    return rows

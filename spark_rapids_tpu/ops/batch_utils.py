"""Batch-level utilities: concat, compact, slice — the cuDF ``Table.concat``/
``contiguousSplit`` analogs (used by GpuCoalesceBatches.scala and
GpuPartitioning.scala in the reference).

Concat is sync-free: capacities are static so the result shape is known
without reading device data; the selection masks ride along.  Compaction
(gathering live rows to the front) is the one place a device→host sync may
happen, because the new ``num_rows`` must become a static Python int — the
same boundary where the reference synchronizes to build output batches.
On the device a compaction is one program (``_compact_program``): it finds
the source row of each output slot once and gathers every array by it, so
its cost follows the slots it fills, not the padded rows it reads.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..batch import (ColumnBatch, DeviceColumn, DictStringColumn,
                     HostStringColumn, Schema, bucket_capacity)
from ..utils.metrics import fetch, fetch_scalars

__all__ = ["concat_batches", "concat_packed", "compact", "slice_batch",
           "gather"]


def _pad_dev(arr: jax.Array, cap: int):
    if arr.shape[0] == cap:
        return arr
    pad = [(0, cap - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, pad)


def concat_batches(batches: Sequence[ColumnBatch],
                   min_capacity: int = 1024) -> ColumnBatch:
    """Concatenate batches (same schema) without compacting or syncing."""
    assert batches, "cannot concat zero batches"
    if len(batches) == 1:
        return batches[0]
    schema = batches[0].schema
    total = sum(b.capacity for b in batches)
    cap = bucket_capacity(total, min_capacity)
    # classify columns: device-concat (one jitted program for ALL of
    # them + the selection mask — the eager version compiled a
    # concatenate+pad per column per shape combination) vs host strings
    col_kind = []
    for ci in range(len(schema)):
        parts = [b.columns[ci] for b in batches]
        if all(isinstance(p, DictStringColumn) for p in parts) and \
                all(p.dictionary is parts[0].dictionary for p in parts):
            col_kind.append("dict")
        elif isinstance(parts[0], HostStringColumn):
            col_kind.append("host")
        else:
            col_kind.append("dev")
    spec = []
    feed = []
    for bi, b in enumerate(batches):
        entry = []
        for ci, kind in enumerate(col_kind):
            c = b.columns[ci]
            if kind == "dict":
                entry.append((c.codes, c.valid))
                spec.append((bi, ci, c.codes.dtype.name,
                             c.valid is not None, ()))
            elif kind == "dev":
                entry.append((c.data, c.valid))
                spec.append((bi, ci, c.data.dtype.name,
                             c.valid is not None,
                             tuple(c.data.shape[1:])))
            else:
                entry.append(None)
        feed.append((tuple(entry), b.sel))
    caps = tuple(b.capacity for b in batches)
    sels_present = tuple(b.sel is not None for b in batches)
    outs, sel = _concat_fn(caps, cap, tuple(col_kind),
                           tuple(spec), sels_present)(
        tuple(f[0] for f in feed), tuple(f[1] for f in feed),
        tuple(np.int32(b.num_rows) for b in batches))
    cols = []
    oi = 0
    host_masks: dict = {}  # ONE mask fetch per batch, shared by columns

    def _mask_of(bi, b):
        if bi not in host_masks:
            host_masks[bi] = fetch(b.active_mask())[: b.num_rows]
        return host_masks[bi]

    for ci, kind in enumerate(col_kind):
        f = schema.fields[ci]
        if kind == "host":
            import pyarrow as pa
            arrs = []
            for bi, b in enumerate(batches):
                p = b.columns[ci]
                a = p.array.slice(0, b.num_rows)
                if b.sel is not None:
                    a = a.filter(pa.array(_mask_of(bi, b)))
                arrs.append(a)
            cat = pa.concat_arrays(arrs)
            if len(cat) < cap:
                cat = pa.concat_arrays(
                    [cat, pa.nulls(cap - len(cat), type=cat.type)])
            cols.append(HostStringColumn(cat))
            continue
        data, valid = outs[oi]
        oi += 1
        if kind == "dict":
            cols.append(DictStringColumn(
                data, valid, batches[0].columns[ci].dictionary))
        else:
            cols.append(DeviceColumn(f.dtype, data, valid))
    has_strings = any(k == "host" for k in col_kind)
    if has_strings:
        # host strings were compacted; device columns were not — mixed batches
        # must compact device side too for row alignment.
        out = ColumnBatch(schema, [c for c in cols], total, sel)
        return compact(out, align_host_strings=True)
    out = ColumnBatch(schema, cols, total, sel)
    bounds = [getattr(b, "bound", None) for b in batches]
    if all(x is not None for x in bounds):
        out.bound = sum(bounds)
    return out


def concat_packed(batches: Sequence[ColumnBatch]) -> ColumnBatch:
    """Concatenate COMPACT batches live rows to live rows.

    A compact batch has no selection mask: its live rows are its first
    ``num_rows``, a count the host holds.  So the result can take the
    rung over the SUM OF THE LIVE ROWS, not over the sum of capacities
    as :func:`concat_batches` must, and what runs over it (a group sort,
    segment sums) pays for rows, not for each part's slack under its own
    rung.  Nothing is fetched.  Where that rung is no smaller, or a batch
    carries a mask or a column that is not a plain device one, this IS
    ``concat_batches``."""
    total = sum(b.num_rows for b in batches)
    cap = bucket_capacity(total, 1024)    # concat_batches' floor
    if len(batches) < 2 \
            or cap >= bucket_capacity(sum(b.capacity for b in batches),
                                      1024) \
            or any(b.sel is not None or type(c) is not DeviceColumn
                   for b in batches for c in b.columns):
        return concat_batches(batches)
    spec = tuple((c.data.dtype.name, tuple(c.data.shape[1:]),
                  tuple(b.columns[ci].valid is not None for b in batches))
                 for ci, c in enumerate(batches[0].columns))
    outs = _concat_packed_fn(tuple(b.capacity for b in batches), cap, spec)(
        tuple(tuple((c.data, c.valid) for c in b.columns) for b in batches),
        tuple(np.int32(b.num_rows) for b in batches))
    cols = [DeviceColumn(f.dtype, d, v)
            for f, (d, v) in zip(batches[0].schema, outs)]
    return ColumnBatch(batches[0].schema, cols, total)


def gather(batch: ColumnBatch, indices: jax.Array, num_rows: int,
           sel: Optional[jax.Array] = None) -> ColumnBatch:
    """Row-gather into a new batch (indices beyond num_rows are padding).

    Each ``x[indices]`` is an EAGER gather, a small jitted dispatch of
    its own per array: the ``eager:gather`` span holds them, so the time
    the host spends inside the JAX runtime here is the account's
    ``dispatch`` and not the calling operator's own."""
    from ..utils import tracing
    with tracing.span(None, "eager:gather", "program"):
        return _gather(batch, indices, num_rows, sel)


def _gather(batch: ColumnBatch, indices: jax.Array, num_rows: int,
            sel: Optional[jax.Array]) -> ColumnBatch:
    cols = []
    host_idx = None
    for f, c in zip(batch.schema, batch.columns):
        if isinstance(c, DictStringColumn):
            codes = c.codes[indices]
            gv = c.valid[indices] if c.valid is not None else None
            cols.append(DictStringColumn(codes, gv, c.dictionary))
            continue
        if isinstance(c, HostStringColumn):
            if host_idx is None:
                host_idx = fetch(indices)
            import pyarrow as pa
            taken = c.array.take(pa.array(np.clip(host_idx, 0, c.capacity - 1),
                                          type=pa.int32()))
            cols.append(HostStringColumn(taken))
        else:
            data = c.data[indices]
            valid = c.valid[indices] if c.valid is not None else None
            cols.append(DeviceColumn(f.dtype, data, valid))
    return ColumnBatch(batch.schema, cols, num_rows, sel)


def compact(batch: ColumnBatch, align_host_strings: bool = False,
            min_capacity: int = 1,
            n_live: Optional[int] = None) -> ColumnBatch:
    """Gather live rows to the front; drops the selection mask.

    Syncs once to learn the live-row count (static for downstream
    planning) unless the caller already knows it and passes ``n_live``
    (e.g. CoalesceBatchesExec batches its per-input counts into one
    fetch).  ``min_capacity`` lets callers force a shared output bucket
    across many compacts (e.g. one per shuffle partition) so XLA compiles
    the gather once instead of once per row-count bucket.
    """
    if batch.sel is None and not align_host_strings:
        return batch
    active = batch.active_mask()
    # host string columns need the mask on host anyway: ONE fetch serves
    # both the live count and the arrow filter (two round trips before)
    host_mask = None
    needs_mask = (not align_host_strings) and any(
        isinstance(c, HostStringColumn)
        and not isinstance(c, DictStringColumn) for c in batch.columns)
    if n_live is None:
        if needs_mask:
            n_live_d, host_mask = fetch((jnp.sum(active), active))
            n_live = int(n_live_d)
        else:
            n_live = fetch_scalars(jnp.sum(active))[0]
    elif needs_mask:
        host_mask = fetch(active)
    # stable compaction WITHOUT a sort: the (j+1)-th live row is the
    # first row where cumsum(active) reaches j+1, so one cumsum gives the
    # source row of every output slot and each column is one gather —
    # and the WHOLE compact (all device columns) runs as ONE cached
    # jitted program, not a dispatch per column.
    new_cap = bucket_capacity(max(n_live, min_capacity))
    dev_inputs = []   # (data, valid) in column order, None for host cols
    spec = []
    for c in batch.columns:
        if isinstance(c, DictStringColumn):
            dev_inputs.append((c.codes, c.valid))
            spec.append(("d", c.codes.dtype.name, c.valid is not None, ()))
        elif isinstance(c, HostStringColumn):
            dev_inputs.append(None)
            spec.append(("h", "", False, ()))
        else:
            dev_inputs.append((c.data, c.valid))
            spec.append(("d", c.data.dtype.name, c.valid is not None,
                         tuple(c.data.shape[1:])))
    outs = _compact_program(
        _compact_form(batch.capacity, new_cap), batch.capacity, new_cap,
        tuple(spec), batch.sel is not None)(
            tuple(dev_inputs), batch.sel, np.int32(batch.num_rows))
    cols = []
    oi = 0
    for (kind, _dt, _hv, _extra), c, f in zip(spec, batch.columns,
                                              batch.schema):
        if kind == "h":
            if align_host_strings:
                # already compacted during concat; repad to new capacity
                import pyarrow as pa
                a = c.array.slice(0, n_live)
                if len(a) < new_cap:
                    a = pa.concat_arrays(
                        [a.combine_chunks() if hasattr(a, "combine_chunks") else a,
                         pa.nulls(new_cap - len(a), type=a.type)])
                cols.append(HostStringColumn(a))
            else:
                import pyarrow as pa
                m = host_mask if host_mask is not None else fetch(active)
                host_mask = m
                a = c.array.filter(pa.array(m))
                if len(a) < new_cap:
                    a = pa.concat_arrays([a, pa.nulls(new_cap - len(a), type=a.type)])
                cols.append(HostStringColumn(a))
            continue
        data, valid = outs[oi]
        oi += 1
        if isinstance(c, DictStringColumn):
            cols.append(DictStringColumn(data, valid, c.dictionary))
        else:
            cols.append(DeviceColumn(f.dtype, data, valid))
    return ColumnBatch(batch.schema, cols, n_live)


import functools


@functools.lru_cache(maxsize=512)
def _concat_fn(caps: tuple, out_cap: int, col_kind: tuple, spec: tuple,
               sels_present: tuple):
    """One jitted program concatenating every device column of N
    batches plus the combined selection mask."""
    n_b = len(caps)
    # (spec participates only as the lru_cache trace key)

    from ..plan.physical import program

    @program("batch_concat")
    def f(entries, sels, num_rows_tuple):
        actives = []
        for bi in range(n_b):
            a = jnp.arange(caps[bi], dtype=jnp.int32) < num_rows_tuple[bi]
            if sels[bi] is not None:
                a = a & sels[bi]
            actives.append(a)
        outs = []
        for ci, kind in enumerate(col_kind):
            if kind == "host":
                continue
            datas, valids = [], []
            any_valid = any(
                entries[bi][ci] is not None
                and entries[bi][ci][1] is not None for bi in range(n_b))
            for bi in range(n_b):
                d, v = entries[bi][ci]
                datas.append(d)
                if any_valid:
                    valids.append(v if v is not None
                                  else jnp.ones((caps[bi],), dtype=bool))
            data = _pad_dev(jnp.concatenate(datas), out_cap)
            valid = _pad_dev(jnp.concatenate(valids), out_cap) \
                if any_valid else None
            outs.append((data, valid))
        sel = _pad_dev(jnp.concatenate(actives), out_cap)
        return tuple(outs), sel

    return f


@functools.lru_cache(maxsize=512)
def _concat_packed_fn(caps: tuple, out_cap: int, spec: tuple):
    """One jitted program laying N compact batches end to end: batch i
    is written whole at the sum of the live rows before it, in order, so
    the slack past its live rows is overwritten by batch i+1.  The work
    array is ``max(caps)`` longer than the result: an update that ran
    past the end would be clamped back over live rows."""
    from ..plan.physical import program
    work = out_cap + max(caps)

    @program("batch_concat_packed")
    def f(entries, num_rows_tuple):
        starts = [jnp.int32(0)]
        for n in num_rows_tuple[:-1]:
            starts.append(starts[-1] + n)
        outs = []
        for ci, (dt, extra, valids_present) in enumerate(spec):
            data = jnp.zeros((work,) + extra, dtype=dt)
            valid = jnp.zeros((work,), dtype=bool) \
                if any(valids_present) else None
            for bi, start in enumerate(starts):
                d, v = entries[bi][ci]
                data = jax.lax.dynamic_update_slice_in_dim(
                    data, d, start, axis=0)
                if valid is not None:
                    valid = jax.lax.dynamic_update_slice_in_dim(
                        valid, v if v is not None
                        else jnp.ones((caps[bi],), dtype=bool), start,
                        axis=0)
            outs.append((data[:out_cap],
                         None if valid is None else valid[:out_cap]))
        return tuple(outs)

    return f


# The two ways _compact_program finds ``src`` (the source row of each
# output slot), each under its own program name so a device trace, the
# ``program:<name>`` events and the compile ledger say which one ran.
COMPACT_FORMS = ("batch_compact", "batch_compact_scatter")

# What the two cost on one TPU v5e, in ns (PERF.md section 6, PR 27's
# grid): a step of the binary search is a new_cap-long gather out of a
# cap-long table, dearer once the table is past 2^23 rows (32 MB); the
# scatter walks every source row once, live or not.
_SEARCH_STEP_NS = 7.8
_SEARCH_STEP_NS_PAST_2_23 = 19.0
_SCATTER_ROW_NS = 5.2
# The same grid's other prices, for scatter_rung: a scatter of a 64-bit
# array (a pair of 32-bit arrays on this chip) by source row, a gather
# by element (bool 8.2, int32 7.4; 64-bit 16.0), a cumulative sum by row.
_SCATTER64_ROW_NS = 70.3
_GATHER_NS = 8.2
_GATHER64_NS = 16.0
_CUMSUM_ROW_NS = 0.32
# below this a whole scatter is not worth a branch: a couple of ms
_RUNG_FLOOR_NS = 2e6
# the rung is cap >> this: Q3's 10,000 live of 2,097,152 rows and the
# stars' few hundred of 262,144 fit it (PERF.md section 6, PR 30)
_RUNG_SHIFT = 6


def _search_ns(cap: int, new_cap: int) -> float:
    step_ns = _SEARCH_STEP_NS if cap <= 1 << 23 \
        else _SEARCH_STEP_NS_PAST_2_23
    return new_cap * cap.bit_length() * step_ns


def _compact_form(cap: int, new_cap: int) -> str:
    """Which form compacts ``cap`` rows into ``new_cap`` slots: search
    where the slots are few against the rows (an aggregation's 100
    groups out of a 16.7M-slot table), one scatter where most rows stay
    (a filter, a join's output).  A pure function of what is static at
    trace time; the arrays do not enter, both forms gather each alike."""
    return COMPACT_FORMS[_search_ns(cap, new_cap) >= cap * _SCATTER_ROW_NS]


def scatter_rung(cap: int, n64: int, n32: int) -> Optional[int]:
    """The capacity below ``cap`` at which a program that scatters
    ``cap`` rows ``n64`` times at 64 bits and ``n32`` times at 32 or 8
    may run instead, when its live rows fit: it then pays a cumulative
    sum over ``cap`` rows, the search (or scatter) for the rung's source
    rows, a gather of the slot and of each scattered array, and the
    scatters over the rung alone.  A power of two, about ``cap / 64``;
    ``None`` where the whole scatter is cheap or the rung would not pay.
    A pure function of what is static at trace time, from the prices
    above, as :func:`_compact_form` is."""
    row_ns = n64 * _SCATTER64_ROW_NS + n32 * _SCATTER_ROW_NS
    if cap * row_ns < _RUNG_FLOOR_NS:
        return None
    r = 1 << max((cap >> _RUNG_SHIFT).bit_length() - 1, 0)
    find_ns = min(_search_ns(cap, r), cap * _SCATTER_ROW_NS)
    gather_ns = (n64 + 1) * _GATHER64_NS + n32 * _GATHER_NS
    pays = cap * _CUMSUM_ROW_NS + find_ns + r * (gather_ns + row_ns) \
        < cap * row_ns
    return r if pays else None


def live_sources(form: str, active: jax.Array, new_cap: int,
                 csum: Optional[jax.Array] = None) -> jax.Array:
    """``src[j]`` = the position of the (j+1)-th live row of ``active``,
    for ``new_cap`` slots; ``cap`` for the slots past the last.  Stable:
    the live rows keep their order.  ``form`` is one of
    :data:`COMPACT_FORMS` (:func:`_compact_form` names the cheaper);
    ``csum`` is ``cumsum(active)`` as int32 where the caller has it."""
    cap = active.shape[0]
    if csum is None:
        csum = jnp.cumsum(active.astype(jnp.int32))
    if form == "batch_compact":
        # log2(cap) steps of a new_cap-long gather
        return jnp.searchsorted(
            csum, jnp.arange(1, new_cap + 1, dtype=jnp.int32),
            side="left")
    # one cap-long scatter for the whole batch
    return jnp.full((new_cap,), cap, dtype=jnp.int32).at[
        jnp.where(active, csum - 1, new_cap)].set(
            jnp.arange(cap, dtype=jnp.int32), mode="drop")


@functools.lru_cache(maxsize=512)
def _compact_program(form: str, cap: int, new_cap: int, spec: tuple,
                     has_sel: bool):
    """One jitted program compacting EVERY device column of a batch:
    the source row of each output slot is found ONCE
    (:func:`live_sources`), then every array is gathered by it; the
    slots past the live rows read zeros and ``False``."""

    from ..plan.physical import program

    @program(form)
    def f(cols, sel, num_rows):
        active = jnp.arange(cap, dtype=jnp.int32) < num_rows
        if sel is not None:
            active = active & sel
        src = live_sources(form, active, new_cap)
        outs = []
        for (kind, _dt, _hv, _extra), dv in zip(spec, cols):
            if kind == "h":
                continue
            data, valid = dv
            od = jnp.take(data, src, axis=0, mode="fill", fill_value=0)
            ov = None
            if valid is not None:
                ov = jnp.take(valid, src, mode="fill", fill_value=False)
            outs.append((od, ov))
        return tuple(outs)

    return f


def compact_packed(batch: ColumnBatch,
                   bound: Optional[int] = None) -> ColumnBatch:
    """Compact a batch whose LIVE ROWS ARE ALREADY FRONT-PACKED (the
    selection mask is a prefix mask, e.g. group_reduce outputs): one mask
    sum + a slice, instead of compact()'s cumsum over every padded row,
    search for the live ones and gather.

    With ``bound`` (a static upper limit on live rows, e.g. the dense-grid
    group count), the compaction is SYNC-FREE: a static slice to the
    bound's capacity bucket, selection mask riding along.  A host sync
    stalls the dispatch front until the device drains, so bounded
    operators must never pay one per batch."""
    if batch.sel is None:
        return batch
    if bound is not None:
        cap = bucket_capacity(min(bound, batch.capacity))
        if cap >= batch.capacity:
            # still bounded: downstream sync-free paths depend on it
            batch.bound = bound
            return batch
        cols = []
        for f, c in zip(batch.schema, batch.columns):
            if isinstance(c, HostStringColumn):
                cols.append(HostStringColumn(c.array.slice(0, cap)))
            else:
                valid = c.valid[:cap] if c.valid is not None else None
                cols.append(DeviceColumn(f.dtype, c.data[:cap], valid))
        out = ColumnBatch(batch.schema, cols, min(batch.num_rows, cap),
                          batch.sel[:cap])
        out.bound = bound
        return out
    n_live = fetch_scalars(jnp.sum(batch.active_mask()))[0]
    sliced = ColumnBatch(batch.schema, batch.columns,
                         min(batch.num_rows, n_live))
    return slice_batch(sliced, 0, n_live)


def slice_batch(batch: ColumnBatch, start: int, length: int) -> ColumnBatch:
    """Device slice (rows must be compact — no selection mask): ONE
    jitted program per (shape spec, out bucket) with the start as a
    dynamic argument — the eager version compiled a dynamic_slice + pad
    per column per (start, length) combination (16 of q3's 110 cold
    compiles)."""
    assert batch.sel is None, "slice requires a compacted batch"
    cap = bucket_capacity(length)
    spec = []
    feed = []
    for c in batch.columns:
        if isinstance(c, DictStringColumn):
            feed.append((c.codes, c.valid))
            spec.append(("d", c.codes.dtype.name, c.valid is not None, ()))
        elif isinstance(c, HostStringColumn):
            feed.append(None)
            spec.append(("h", "", False, ()))
        else:
            feed.append((c.data, c.valid))
            spec.append(("d", c.data.dtype.name, c.valid is not None,
                         tuple(c.data.shape[1:])))
    outs = _slice_fn(batch.capacity, cap, tuple(spec))(
        tuple(feed), np.int32(start))
    cols = []
    oi = 0
    for (kind, _dt, _hv, _ex), c, f in zip(spec, batch.columns,
                                           batch.schema):
        if kind == "h":
            a = c.array.slice(start, length)
            import pyarrow as pa
            if len(a) < cap:
                a = pa.concat_arrays([a.combine_chunks() if isinstance(
                    a, pa.ChunkedArray) else a,
                    pa.nulls(cap - len(a), type=a.type)])
            cols.append(HostStringColumn(a))
            continue
        data, valid = outs[oi]
        oi += 1
        if isinstance(c, DictStringColumn):
            cols.append(DictStringColumn(data, valid, c.dictionary))
        else:
            cols.append(DeviceColumn(f.dtype, data, valid))
    return ColumnBatch(batch.schema, cols, length)


@functools.lru_cache(maxsize=512)
def _slice_fn(cap: int, out_cap: int, spec: tuple):
    """Jitted whole-batch slice: static output size, dynamic start.
    Data pads by out_cap first so dynamic_slice never clamps the start
    (a clamped start would bleed garbage into live rows)."""

    from ..plan.physical import program

    @program("batch_slice")
    def f(cols, start):
        outs = []
        for (kind, _dt, _hv, extra), dv in zip(spec, cols):
            if kind == "h":
                continue
            data, valid = dv
            pad = [(0, out_cap)] + [(0, 0)] * (data.ndim - 1)
            d = jax.lax.dynamic_slice_in_dim(
                jnp.pad(data, pad), start, out_cap)
            v = None
            if valid is not None:
                v = jax.lax.dynamic_slice_in_dim(
                    jnp.pad(valid, (0, out_cap)), start, out_cap)
            outs.append((d, v))
        return tuple(outs)

    return f

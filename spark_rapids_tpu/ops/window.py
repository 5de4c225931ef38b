"""Segmented window kernels: the device compute behind WindowExec.

TPU-native analog of the reference's window machinery (GpuWindowExec.scala:1329
batched / :1655 running / :2004 double-pass; GpuWindowExpression.scala frame
lowering).  The reference dispatches per-frame cuDF window aggregations; on
TPU a window computes as ONE fused XLA program over the whole sorted input:

  * rows are sorted by (partition keys, order keys) — reusing the group-by
    sort machinery (ops/groupby.py);
  * partitions and order-peer groups become *segments* (boundary masks +
    running ids), all static-shape;
  * every window function is then a segmented scan/reduce: row_number is an
    index difference, running aggregates are segment-reset prefix scans
    (``jax.lax.associative_scan`` with a reset flag), sliding ROWS frames are
    prefix-sum differences, whole-partition frames are segment reductions
    gathered back by segment id.

Everything fuses: a query computing five window columns over one spec costs
one sort + one fused scan pass, not five kernel launches.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import groupby

Value = Tuple[jax.Array, Optional[jax.Array]]


class SortedWindowContext:
    """Traced per-batch window state over the sorted row order.

    Built once per (partition_by, order_by) spec inside the jitted window
    program; all window expressions for that spec share it.
    """

    def __init__(self, part_keys: List[Value], order_keys: List[Value],
                 order_desc: Sequence[bool], order_nulls_first: Sequence[bool],
                 active: jax.Array):
        cap = active.shape[0]
        self.capacity = cap
        self.arange = jnp.arange(cap, dtype=jnp.int32)
        keys = part_keys + order_keys
        desc = [False] * len(part_keys) + list(order_desc)
        nf = [True] * len(part_keys) + list(order_nulls_first)
        # stable passes, not one lexsort: a window's sort holds a key a
        # partition column more, and its compile time must not double
        # with each (ops/groupby._sort_passes)
        with jax.named_scope("window_sort"):
            self.perm = groupby.sort_indices_for_keys(keys, active, desc,
                                                      nf, passes=True)
        self._segments(part_keys, order_keys, active)

    @jax.named_scope("window_segments")
    def _segments(self, part_keys, order_keys, active):
        cap = self.capacity
        self.active = active[self.perm]
        s_part = [(d[self.perm], None if v is None else v[self.perm])
                  for d, v in part_keys]
        s_ord = [(d[self.perm], None if v is None else v[self.perm])
                 for d, v in order_keys]

        self.seg_start = groupby._segment_starts(s_part, self.active)
        self.seg_ids = jnp.cumsum(self.seg_start.astype(jnp.int32)) - 1
        self.seg_ids = jnp.where(self.active, self.seg_ids, cap - 1)
        self.seg_start_pos = jax.lax.cummax(
            jnp.where(self.seg_start, self.arange, 0))
        # last row of each segment: next row starts a new one, or is inactive
        boundary = jnp.roll(self.seg_start, -1).at[-1].set(True)
        inact_next = jnp.roll(~self.active, -1).at[-1].set(True)
        self.seg_last = (boundary | inact_next) & self.active
        end_cand = jnp.where(self.seg_last, self.arange, cap - 1)
        self.seg_end_pos = jnp.flip(jax.lax.cummin(jnp.flip(end_cand)))

        # order-peer groups (ties in the order keys, within a partition)
        self.peer_start = groupby._segment_starts(s_part + s_ord, self.active)
        self.peer_start_pos = jax.lax.cummax(
            jnp.where(self.peer_start, self.arange, 0))
        p_boundary = jnp.roll(self.peer_start, -1).at[-1].set(True)
        self.peer_last = (p_boundary | inact_next) & self.active
        pend = jnp.where(self.peer_last, self.arange, cap - 1)
        self.peer_end_pos = jnp.flip(jax.lax.cummin(jnp.flip(pend)))

    # -- positional helpers ---------------------------------------------------------
    def sort_value(self, val: Value) -> Value:
        d, v = val
        return d[self.perm], (None if v is None else v[self.perm])

    def unsort(self, data: jax.Array) -> jax.Array:
        """Inverse-permute a sorted-order column back to input order."""
        inv = jnp.zeros_like(self.perm).at[self.perm].set(
            jnp.arange(self.capacity, dtype=self.perm.dtype))
        return data[inv]


# ------------------------------------------------------------------------------------
# Ranking kernels (values in sorted order)
# ------------------------------------------------------------------------------------

def row_number(w: SortedWindowContext) -> jax.Array:
    return (w.arange - w.seg_start_pos + 1).astype(jnp.int32)


def rank(w: SortedWindowContext) -> jax.Array:
    return (w.peer_start_pos - w.seg_start_pos + 1).astype(jnp.int32)


def dense_rank(w: SortedWindowContext) -> jax.Array:
    dcum = jnp.cumsum(w.peer_start.astype(jnp.int32))
    return (dcum - dcum[w.seg_start_pos] + 1).astype(jnp.int32)


def percent_rank(w: SortedWindowContext) -> jax.Array:
    n = (w.seg_end_pos - w.seg_start_pos).astype(jnp.float64)  # rows - 1
    r = (rank(w) - 1).astype(jnp.float64)
    return jnp.where(n > 0, r / jnp.where(n > 0, n, 1.0), 0.0)


def cume_dist(w: SortedWindowContext) -> jax.Array:
    n = (w.seg_end_pos - w.seg_start_pos + 1).astype(jnp.float64)
    r = (w.peer_end_pos - w.seg_start_pos + 1).astype(jnp.float64)
    return r / n


def ntile(w: SortedWindowContext, n: int) -> jax.Array:
    """Spark NTile: first ``size % n`` buckets get one extra row."""
    size = w.seg_end_pos - w.seg_start_pos + 1
    rn0 = w.arange - w.seg_start_pos
    base = size // n
    rem = size % n
    big = base + 1
    in_big = rn0 < big * rem
    big_safe = jnp.maximum(big, 1)
    base_safe = jnp.maximum(base, 1)
    tile = jnp.where(in_big, rn0 // big_safe,
                     rem + (rn0 - big * rem) // base_safe)
    return (tile + 1).astype(jnp.int32)


def shift(w: SortedWindowContext, val_sorted: Value, offset: int,
          default: Optional[Value] = None) -> Value:
    """lag (offset>0) / lead (offset<0) within the partition."""
    d, v = val_sorted
    src = w.arange - jnp.int32(offset)
    in_seg = (src >= w.seg_start_pos) & (src <= w.seg_end_pos) & w.active
    safe = jnp.clip(src, 0, w.capacity - 1)
    out = d[safe]
    valid = in_seg if v is None else (in_seg & v[safe])
    if default is not None:
        dd, dv = default
        dd = dd.astype(out.dtype) if dd.dtype != out.dtype else dd
        out = jnp.where(in_seg, out, dd)
        if dv is None:
            valid = jnp.where(in_seg, valid, True)
        else:
            valid = jnp.where(in_seg, valid, dv)
    return out, valid


# ------------------------------------------------------------------------------------
# Segmented scans for running aggregates
# ------------------------------------------------------------------------------------

def _segmented_scan(vals: jax.Array, seg_start: jax.Array, combine):
    """Inclusive segmented scan: resets at each segment start."""

    def op(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, combine(av, bv)), af | bf

    out, _ = jax.lax.associative_scan(op, (vals, seg_start))
    return out


def running_sum(w: SortedWindowContext, contrib: jax.Array) -> jax.Array:
    c = jnp.cumsum(contrib, dtype=contrib.dtype)
    base = c[w.seg_start_pos] - contrib[w.seg_start_pos]
    return c - base


def running_minmax(w: SortedWindowContext, data: jax.Array, m: jax.Array,
                   op: str) -> jax.Array:
    kind = ("f" if jnp.issubdtype(data.dtype, jnp.floating)
            else "b" if data.dtype == jnp.bool_ else "i")
    sentinel = groupby._SENTINELS[op][kind](data.dtype)
    vals = jnp.where(m, data, jnp.full_like(data, sentinel))
    fn = jnp.minimum if op == "min" else jnp.maximum
    return _segmented_scan(vals, w.seg_start, fn)


def partition_reduce(w: SortedWindowContext, contrib: jax.Array, m: jax.Array,
                     op: str) -> jax.Array:
    """Whole-partition reduce, broadcast back to every row."""
    if op == "sum":
        vals = jnp.where(m, contrib, jnp.zeros_like(contrib))
        tot = jax.ops.segment_sum(vals, w.seg_ids, num_segments=w.capacity)
    else:
        kind = ("f" if jnp.issubdtype(contrib.dtype, jnp.floating)
                else "b" if contrib.dtype == jnp.bool_ else "i")
        sentinel = groupby._SENTINELS[op][kind](contrib.dtype)
        vals = jnp.where(m, contrib, jnp.full_like(contrib, sentinel))
        f = jax.ops.segment_min if op == "min" else jax.ops.segment_max
        tot = f(vals, w.seg_ids, num_segments=w.capacity)
    return tot[w.seg_ids]


def rows_positions(w: SortedWindowContext, lo: Optional[int],
                   hi: Optional[int]):
    """[lo_pos, hi_pos] index window of a ROWS frame, partition-clamped."""
    i = w.arange
    lo_pos = w.seg_start_pos if lo is None else jnp.maximum(
        i + jnp.int32(lo), w.seg_start_pos)
    hi_pos = w.seg_end_pos if hi is None else jnp.minimum(
        i + jnp.int32(hi), w.seg_end_pos)
    return lo_pos, hi_pos


def range_positions(w: SortedWindowContext, key: jax.Array,
                    key_valid: Optional[jax.Array],
                    lo: Optional[int], hi: Optional[int],
                    descending: bool = False,
                    nulls_first: bool = True,
                    wide: bool = False):
    """[lo_pos, hi_pos] of a value-RANGE frame over a single order key
    (GpuWindowExec.scala:1655 bounded range analog).

    int32-representable keys (int/date) pack into ONE int64 composite —
    (segment_id << 35) | (null_block_flag << 34) | 33-bit biased key —
    and resolve with two native searchsorted passes; 64-bit keys
    (bigint/timestamp, ``wide=True``) use a vectorized lexicographic
    binary search over (segment, null-block, key) instead (no packing
    exists for them).  Descending orders negate the key, which maps
    Spark's desc-range semantics (PRECEDING adds) onto the ascending
    kernel exactly.  NULL-keyed rows form their own peer group (Spark
    semantics): their frame is exactly the segment's null block, placed
    per ``nulls_first``."""
    k64 = key.astype(jnp.int64)
    if descending:
        k64 = -k64
    ok = (jnp.ones_like(k64, dtype=bool) if key_valid is None
          else key_valid)
    # flag orders the null block to match the physical sort: nulls first
    # -> nulls get 0 / values 1; nulls last -> values 0 / nulls 1
    val_flag = jnp.int64(1) if nulls_first else jnp.int64(0)
    null_flag = jnp.int64(0) if nulls_first else jnp.int64(1)

    def _sat_add(a, delta):
        t = a + jnp.int64(delta)
        if delta >= 0:
            return jnp.where(t < a, jnp.int64(2**62), t)
        return jnp.where(t > a, jnp.int64(-(2**62)), t)

    if wide:
        seg64 = w.seg_ids.astype(jnp.int64)

        def _search(delta, side):
            tgt = _sat_add(k64, delta)
            return _lex_searchsorted(
                w, seg64, jnp.where(ok, val_flag, null_flag), k64,
                seg64, jnp.full_like(seg64, val_flag), tgt, side)

        def _null_edge(side):
            return _lex_searchsorted(
                w, seg64, jnp.where(ok, val_flag, null_flag), k64,
                seg64, jnp.full_like(seg64, null_flag),
                jnp.full_like(k64, -(2**62) if side == "left"
                              else 2**62), side)
    else:
        bias = jnp.int64(1) << 32  # 33-bit field: holds negated int32 min
        seg = w.seg_ids.astype(jnp.int64) << 35
        fb = jnp.int64(1) << 34
        comp = seg | jnp.where(ok, (val_flag << 34) | (k64 + bias),
                               null_flag << 34)
        # inactive rows park at the top so they never enter a window
        comp = jnp.where(w.active, comp, jnp.int64(2**62))
        kmin, kmax = -(2**32) + 1, (2**32) - 1

        def _search(delta, side):
            tgt = jnp.clip(_sat_add(k64, delta), kmin, kmax)
            return jnp.searchsorted(
                comp, seg | (val_flag << 34) | (tgt + bias),
                side=side).astype(jnp.int32)

        def _null_edge(side):
            probe = seg | (null_flag << 34) | (
                jnp.int64(0) if side == "left" else (fb - 1))
            return jnp.searchsorted(comp, probe,
                                    side=side).astype(jnp.int32)

    lo_pos = w.seg_start_pos if lo is None else _search(lo, "left")
    hi_pos = w.seg_end_pos if hi is None else (_search(hi, "right") - 1)
    if key_valid is not None:
        if nulls_first:
            # null block = [seg_start, first valid row)
            if wide:
                seg64 = w.seg_ids.astype(jnp.int64)
                vstart = _lex_searchsorted(
                    w, seg64, jnp.where(ok, val_flag, null_flag), k64,
                    seg64, jnp.full_like(seg64, val_flag),
                    jnp.full_like(k64, -(2**62)), "left")
            else:
                vstart = jnp.searchsorted(
                    comp, seg | (val_flag << 34),
                    side="left").astype(jnp.int32)
            lo_pos = jnp.where(ok, lo_pos, w.seg_start_pos)
            hi_pos = jnp.where(ok, hi_pos, vstart - 1)
        else:
            # null block = [first null row, seg_end]
            nstart = _null_edge("left")
            lo_pos = jnp.where(ok, lo_pos, nstart)
            hi_pos = jnp.where(ok, hi_pos, w.seg_end_pos)
    return lo_pos, hi_pos


def _lex_searchsorted(w: SortedWindowContext, seg, flag, key,
                      tseg, tflag, tkey, side: str) -> jax.Array:
    """Vectorized binary search over rows sorted by (seg, flag, key):
    per-target insertion point, log2(capacity) gather steps."""
    cap = w.capacity
    steps = max(1, int(np.ceil(np.log2(max(cap, 2)))) + 1)
    # pad compare: positions >= cap sort at +inf
    seg_p = jnp.concatenate([seg, jnp.full((1,), 2**62, jnp.int64)])
    flag_p = jnp.concatenate([flag, jnp.full((1,), 2**62, jnp.int64)])
    key_p = jnp.concatenate([key, jnp.full((1,), 2**62, jnp.int64)])
    inactive = ~w.active
    seg_p = seg_p.at[:cap].set(jnp.where(inactive, 2**62, seg_p[:cap]))
    lo0 = jnp.zeros_like(tkey, dtype=jnp.int32)
    hi0 = jnp.full_like(lo0, cap)

    def body(_i, state):
        lo, hi = state
        mid = (lo + hi) >> 1
        ms, mf, mk = seg_p[mid], flag_p[mid], key_p[mid]
        if side == "left":
            less = (ms < tseg) | ((ms == tseg) & (
                (mf < tflag) | ((mf == tflag) & (mk < tkey))))
        else:
            less = (ms < tseg) | ((ms == tseg) & (
                (mf < tflag) | ((mf == tflag) & (mk <= tkey))))
        lo = jnp.where(less, mid + 1, lo)
        hi = jnp.where(less, hi, mid)
        return lo, hi

    lo_f, _ = jax.lax.fori_loop(0, steps, body, (lo0, hi0))
    return lo_f.astype(jnp.int32)


def positional_sum(w: SortedWindowContext, contrib: jax.Array,
                   lo_pos: jax.Array, hi_pos: jax.Array) -> jax.Array:
    """Sum over [lo_pos, hi_pos] via prefix-sum difference."""
    c = jnp.cumsum(contrib, dtype=contrib.dtype)
    empty = hi_pos < lo_pos
    lo_c = jnp.clip(lo_pos, 0, w.capacity - 1)
    hi_c = jnp.clip(hi_pos, 0, w.capacity - 1)
    out = c[hi_c] - c[lo_c] + contrib[lo_c]
    return jnp.where(empty, jnp.zeros_like(out), out)


def sliding_sum(w: SortedWindowContext, contrib: jax.Array,
                lo: Optional[int], hi: Optional[int]) -> jax.Array:
    """ROWS BETWEEN lo AND hi (offsets relative to current row; None=∞).

    Prefix-sum difference clamped to the partition bounds.
    """
    lo_pos, hi_pos = rows_positions(w, lo, hi)
    return positional_sum(w, contrib, lo_pos, hi_pos)


def _mm_sentinel(dtype, op: str):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if op == "min" else -jnp.inf, dtype=dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if op == "min" else info.min, dtype=dtype)


def sliding_minmax(w: SortedWindowContext, data: jax.Array,
                   mask: jax.Array, lo_pos: jax.Array, hi_pos: jax.Array,
                   max_width: int, op: str) -> jax.Array:
    """min/max over [lo_pos, hi_pos] windows via a sparse table: log2(W)
    doubling passes build interval minima of power-of-two widths; each row
    answers with two overlapping lookups (van Emde Boas / sparse-table RMQ
    — the TPU shape for GpuWindowExec's sliding min/max regime).
    ``max_width`` must statically bound hi-lo+1 (frame constants)."""
    sent = _mm_sentinel(data.dtype, op)
    x = jnp.where(mask, data, sent)
    combine = jnp.minimum if op == "min" else jnp.maximum
    cap = w.capacity
    levels = [x]
    shift = 1
    while shift < max_width:
        prev = levels[-1]
        shifted = jnp.concatenate(
            [prev[shift:], jnp.full((shift,), sent, dtype=data.dtype)])
        levels.append(combine(prev, shifted))
        shift <<= 1
    M = jnp.stack(levels)  # (L, cap); level k covers width 2^k
    width = jnp.maximum(hi_pos - lo_pos + 1, 1)
    k = jnp.floor(jnp.log2(width.astype(jnp.float64))).astype(jnp.int32)
    k = jnp.clip(k, 0, len(levels) - 1)
    lo_c = jnp.clip(lo_pos, 0, cap - 1)
    r_idx = jnp.clip(hi_pos - (jnp.int32(1) << k) + 1, 0, cap - 1)
    out = combine(M[k, lo_c], M[k, r_idx])
    return out

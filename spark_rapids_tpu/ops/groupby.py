"""Sort-based group-by reduction kernels.

The reference does hash-based group-by through cuDF (aggregate.scala:376
``performGroupByAggregation``).  Device hash tables are a poor fit for
XLA/TPU, so grouping here is sort-based (SURVEY.md §7.3): lexsort rows by key,
mark segment starts where adjacent keys differ, then reduce with XLA segment
ops.  Everything is static-shape: a batch of capacity C reduces to a batch of
capacity C with ``n_groups`` live rows up front — no dynamic allocation, one
compiled executable per capacity bucket.

Float keys are grouped through a monotonic *sortable integer view* so that
NaN == NaN and -0.0 == 0.0 for grouping purposes (Spark normalizes these —
NormalizeFloatingNumbers.scala).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..types import DataType

Value = Tuple[jax.Array, Optional[jax.Array]]

_SENTINELS = {
    "min": {
        "i": lambda dt: np.iinfo(dt).max,
        "f": lambda dt: np.inf,
        "b": lambda dt: True,
    },
    "max": {
        "i": lambda dt: np.iinfo(dt).min,
        "f": lambda dt: -np.inf,
        "b": lambda dt: False,
    },
}


def sortable_view(data: jax.Array) -> jax.Array:
    """Monotonic integer view of a column for sorting/grouping.

    Floats map to sign-flipped integer bit patterns: total order with all
    NaNs collapsing to one bucket at the top; -0.0 normalized to +0.0.
    """
    if jnp.issubdtype(data.dtype, jnp.floating):
        if data.dtype == jnp.float16:
            data = data.astype(jnp.float32)
        data = jnp.where(data == 0.0, jnp.zeros_like(data), data)  # -0.0 → +0.0
        nan = jnp.isnan(data)
        ibits = jnp.int32 if data.dtype == jnp.float32 else jnp.int64
        if data.dtype == jnp.float64:
            # arithmetic bit extraction: NO 64-bit bitcast-convert exists
            # in XLA's X64-rewrite pass on real TPU backends
            from .hashing import f64_bit_pattern
            bits = f64_bit_pattern(data)
        else:
            bits = jax.lax.bitcast_convert_type(data, ibits)
        # signed total-order key: non-negative floats keep their bits
        # (monotonic, positive); negative floats map to MIN - bits, which is
        # negative and increases as the float increases toward zero.
        imin = jnp.iinfo(ibits).min
        iview = jnp.where(bits < 0, imin - bits, bits)
        big = jnp.iinfo(ibits).max
        return jnp.where(nan, big, iview)  # all NaNs: one group, sorts last
    if data.dtype == jnp.bool_:
        return data.astype(jnp.int32)
    return data


def _null_order_key(valid: Optional[jax.Array], capacity: int) -> jax.Array:
    # Grouping treats null as its own group; order nulls first (arbitrary but
    # stable).  valid=False (null) sorts before valid=True.
    if valid is None:
        return jnp.ones((capacity,), dtype=jnp.int32)
    return valid.astype(jnp.int32)


# one lexsort holds at most this many operands: XLA's compile time for a
# TPU sort doubles per operand (group_sort_indices below has the seconds),
# so an ordering with more keys than any accepted cell sorts by today
# (TPC-DS Q52's top-k: three keys, five operands) runs as stable passes
_LEXSORT_MAX_OPERANDS = 5


def sort_indices_for_keys(keys: Sequence[Value], active: jax.Array,
                          descending: Optional[Sequence[bool]] = None,
                          nulls_first: Optional[Sequence[bool]] = None,
                          passes: bool = False) -> jax.Array:
    """Stable sort permutation: active rows first, ordered by keys.

    ``keys`` are (data, valid) pairs; inactive (filtered/padding) rows sort to
    the end regardless of key value.  ``passes`` asks for the form that
    compiles fast whatever the number of keys (:func:`_sort_passes`); past
    ``_LEXSORT_MAX_OPERANDS`` operands it is taken anyway.
    """
    capacity = active.shape[0]
    arrays = []
    n = len(keys)
    desc = list(descending) if descending is not None else [False] * n
    nf = list(nulls_first) if nulls_first is not None else [True] * n
    # jnp.lexsort sorts by the LAST key first; build minor→major.
    for i in reversed(range(n)):
        data, valid = keys[i]
        if data.ndim == 2:
            # wide-decimal limbs [lo, hi]: true 128-bit order is
            # (hi signed, lo unsigned) lexicographic — two operands,
            # minor (lo) appended first so lexsort treats hi as major
            sign = jnp.int64(np.iinfo(np.int64).min)
            lo_u = data[:, 0] ^ sign  # unsigned order as signed ints
            hi = data[:, 1]
            if desc[i]:
                lo_u = ~lo_u
                hi = ~hi
            vkey = _null_order_key(valid, capacity)
            if not nf[i]:
                vkey = 1 - vkey
            arrays.append(lo_u)
            arrays.append(hi)
            arrays.append(vkey)
            continue
        view = sortable_view(data)
        if desc[i]:
            view = ~view  # bitwise complement: monotonic flip without overflow
        vkey = _null_order_key(valid, capacity)
        # null position: null indicator 0 sorts first under ascending
        # (nulls_first); flip the indicator for nulls_last.
        if not nf[i]:
            vkey = 1 - vkey
        if view.dtype.itemsize <= 4:
            # fold the null indicator into one int64 word: XLA TPU sort
            # compile time roughly doubles per operand (round-4
            # measurement), so every operand saved halves the compile
            view64 = view.astype(jnp.int64) + jnp.int64(2**31)
            arrays.append((vkey.astype(jnp.int64) << jnp.int64(32))
                          + view64)
        else:
            arrays.append(view)
            arrays.append(vkey)
    arrays.append(~active)  # most significant: active rows (False) first
    if passes or len(arrays) > _LEXSORT_MAX_OPERANDS:
        return _sort_passes(arrays)
    return jnp.lexsort(tuple(arrays))


@jax.named_scope("sort_passes")
def _sort_passes(arrays: Sequence[jax.Array]) -> jax.Array:
    """The permutation of ``jnp.lexsort(arrays)`` (minor key first) as one
    stable two-operand sort per 32-bit digit, least significant first: a
    gather a pass, and a compile time that grows by the pass, not by the
    power (a ten-key ORDER BY is a 20-operand lexsort otherwise)."""
    capacity = arrays[0].shape[0]
    perm = jnp.arange(capacity, dtype=jnp.int32)
    for a in arrays:
        if a.dtype == jnp.bool_:
            digits = [a.astype(jnp.uint32)]
        elif a.dtype.itemsize <= 4:
            # signed order as unsigned: flip the sign bit
            digits = [a.astype(jnp.int32).astype(jnp.uint32)
                      ^ jnp.uint32(1 << 31)]
        else:
            u = a.astype(jnp.int64).astype(jnp.uint64) \
                ^ jnp.uint64(1 << 63)
            digits = [(u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
                      (u >> jnp.uint64(32)).astype(jnp.uint32)]
        for digit in digits:
            perm = jax.lax.sort((digit[perm], perm), num_keys=1,
                                is_stable=True)[1]
    return perm


@jax.named_scope("groupby_sort")
def group_sort_indices(keys: Sequence[Value], active: jax.Array) -> jax.Array:
    """Permutation putting EQUAL keys adjacent; order between groups is
    arbitrary.  The grouping paths (group-by, join group-id encoding)
    must use this instead of sort_indices_for_keys: XLA's TPU sort
    compile time roughly doubles per operand (measured on the round-4
    chip: 36 s / 55 s / 329 s for 2 / 3 / 5 operands at 512k rows), and
    the ordering sort carries 2 operands PER KEY (value view + null
    indicator) — a 3-key group-by was a 190 s compile.  Sorting a
    128-bit key hash keeps the operand count at a constant 3.

    Exactness: segment boundaries downstream (_segment_starts) compare
    the TRUE sorted keys, so a hash collision can never merge two
    groups; the only risk is two colliding DISTINCT keys interleaving
    into duplicate group rows, p ≈ pairs / 2^127 — below hardware error
    rates.  Nulls hash via an explicit validity fold (a null and a
    zero-valued row differ).

    The sort is four stable passes of a two-operand sort, one per 32-bit
    digit of the hash from the least significant up: the permutation of
    ``jnp.lexsort((h2, h1))``, whose two 64-bit keys and index are five
    32-bit operands of one sort.  XLA's compile time for a TPU sort
    doubles per operand (34 s for the four passes against 174 s at 1M
    rows, PERF.md section 6), and a program may hold several."""
    from .hashing import _xxhash64_long, xxhash64_value
    capacity = active.shape[0]
    h1 = jnp.full((capacity,), jnp.uint64(0x9E3779B97F4A7C15),
                  dtype=jnp.uint64)
    h2 = jnp.full((capacity,), jnp.uint64(0x5851F42D4C957F2D),
                  dtype=jnp.uint64)
    for data, valid in keys:
        clean = data if valid is None else jnp.where(
            valid, data, jnp.zeros_like(data))
        h1 = xxhash64_value(clean, None, h1)
        h2 = xxhash64_value(clean, None, h2)
        if valid is not None:
            vb = valid.astype(jnp.uint64)
            h1 = _xxhash64_long(vb, h1)
            h2 = _xxhash64_long(vb, h2)
    # inactive rows to the end: reserve the top h1 value
    h1 = jnp.where(active, h1 >> jnp.uint64(1),
                   jnp.uint64(0xFFFFFFFFFFFFFFFF))
    low = jnp.uint64(0xFFFFFFFF)
    perm = jnp.arange(capacity, dtype=jnp.int32)
    for digit in (h2 & low, h2 >> jnp.uint64(32),
                  h1 & low, h1 >> jnp.uint64(32)):
        perm = jax.lax.sort((digit.astype(jnp.uint32)[perm], perm),
                            num_keys=1, is_stable=True)[1]
    return perm


def _segment_starts(sorted_keys: Sequence[Value], sorted_active: jax.Array) -> jax.Array:
    """Boolean mask: row begins a new group (active rows only)."""
    capacity = sorted_active.shape[0]
    first = jnp.zeros((capacity,), dtype=bool).at[0].set(True)
    diff = jnp.zeros((capacity,), dtype=bool)
    for data, valid in sorted_keys:
        view = sortable_view(data)
        prev = jnp.roll(view, 1)
        d = view != prev
        if valid is not None:
            pv = jnp.roll(valid, 1)
            d = d | (valid != pv)
            # two nulls are the same group regardless of payload values
            d = jnp.where(~valid & ~pv, False, d)
        diff = diff | d
    starts = (first | diff) & sorted_active
    return starts


@jax.named_scope("segmented_reduce")
def _reduce_segment(data: jax.Array, valid: Optional[jax.Array], op: str,
                    seg_ids: jax.Array, mask: jax.Array, num_segments: int,
                    seg_start: jax.Array, seg_last: jax.Array) -> Value:
    """Reduce one (sorted) contribution column into per-segment slots."""
    m = mask if valid is None else (mask & valid)
    if op == "sum":
        contrib = jnp.where(m, data, jnp.zeros_like(data))
        out = jax.ops.segment_sum(contrib, seg_ids, num_segments=num_segments)
        return out, None
    if op in ("min", "max"):
        kind = ("f" if jnp.issubdtype(data.dtype, jnp.floating)
                else "b" if data.dtype == jnp.bool_ else "i")
        sentinel = _SENTINELS[op][kind](data.dtype)
        contrib = jnp.where(m, data, jnp.full_like(data, sentinel))
        f = jax.ops.segment_min if op == "min" else jax.ops.segment_max
        out = f(contrib, seg_ids, num_segments=num_segments)
        return out, None
    if op == "first":
        pick = seg_start & mask
        contrib = jnp.where(pick, data, jnp.zeros_like(data))
        out = jax.ops.segment_sum(contrib, seg_ids, num_segments=num_segments)
        v = None
        if valid is not None:
            vout = jax.ops.segment_sum(
                jnp.where(pick, valid, False).astype(jnp.int32), seg_ids,
                num_segments=num_segments)
            v = vout > 0
        return out, v
    if op == "last":
        pick = seg_last & mask
        contrib = jnp.where(pick, data, jnp.zeros_like(data))
        out = jax.ops.segment_sum(contrib, seg_ids, num_segments=num_segments)
        v = None
        if valid is not None:
            vout = jax.ops.segment_sum(
                jnp.where(pick, valid, False).astype(jnp.int32), seg_ids,
                num_segments=num_segments)
            v = vout > 0
        return out, v
    if op in ("first_valid", "last_valid"):
        # first/last(ignore_nulls=True): pick the first/last row in the
        # segment that is both active and non-null (not merely the segment
        # boundary row) via a segment min/max over row indices.
        n = data.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        if op == "first_valid":
            cand = jnp.where(m, idx, n)  # sentinel past the end
            best = jax.ops.segment_min(cand, seg_ids, num_segments=num_segments)
            has = best < n
        else:
            cand = jnp.where(m, idx, -1)
            best = jax.ops.segment_max(cand, seg_ids, num_segments=num_segments)
            has = best >= 0
        safe = jnp.clip(best, 0, n - 1)
        return jnp.where(has, data[safe], jnp.zeros_like(data[safe])), has
    raise ValueError(f"unknown reduce op {op}")


def group_reduce(keys: List[Value], contributions: List[Tuple[Value, str]],
                 active: jax.Array):
    """Group rows by ``keys`` and reduce ``contributions``.

    Returns (out_keys, out_values, n_groups, group_mask) where every output
    array has the input capacity, live group rows packed at the front, and
    ``n_groups`` is a device scalar (int32).

    TPU cost note: on this hardware a 2M-row gather or scatter pass costs
    hundreds of ms *per pass* regardless of width, so all sum-expressible
    reductions (sum / first / last, including key columns and validity
    companions) are STACKED into one float64 and one int64 matrix — one
    batched permutation gather and one batched ``segment_sum`` per family —
    instead of one pass per column.  Only min/max and first_valid/last_valid
    take the per-column fallback.
    """
    capacity = active.shape[0]
    perm = group_sort_indices(keys, active)
    s_active = active[perm]
    s_keys = [(d[perm], (v[perm] if v is not None else None)) for d, v in keys]
    seg_start = _segment_starts(s_keys, s_active)
    seg_ids = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    # Inactive rows (sorted to the end) inherit the running segment id; park
    # them in the last slot instead so they cannot pollute a real group.
    seg_ids = jnp.where(s_active, seg_ids, capacity - 1)
    boundary = jnp.roll(seg_start, -1).at[-1].set(True)
    seg_last = (boundary | jnp.roll(~s_active, -1).at[-1].set(True)) & s_active

    n_groups = jnp.sum(seg_start.astype(jnp.int32))

    # ---- batched sum-family machinery ------------------------------------------
    # Stage 1: queue every column (data + validity) for ONE permutation
    # gather per dtype family.  Stage 2: queue masked contributions for ONE
    # segment_sum per family.  Handles are (family, index) into the results.
    raw_f64: List[jax.Array] = []
    raw_i64: List[jax.Array] = []

    def _queue_raw(arr) -> tuple:
        if jnp.issubdtype(arr.dtype, jnp.floating):
            raw_f64.append(arr.astype(jnp.float64))
            return ("f", len(raw_f64) - 1)
        raw_i64.append(arr.astype(jnp.int64))
        return ("i", len(raw_i64) - 1)

    # queue: keys' data already sorted (s_keys); contributions raw
    batched_specs: List = []   # one per contribution, or ("fallback", i)
    for i, ((d, v), op) in enumerate(contributions):
        if op in ("sum", "first", "last"):
            batched_specs.append(
                ("batched", op, _queue_raw(d),
                 _queue_raw(v) if v is not None else None, d.dtype))
        else:
            batched_specs.append(("fallback", i))

    sorted_cols: dict = {}
    if raw_f64:
        g = (raw_f64[0][perm] if len(raw_f64) == 1 else
             jnp.stack(raw_f64, axis=1)[perm])
        for i in range(len(raw_f64)):
            sorted_cols[("f", i)] = g if len(raw_f64) == 1 else g[:, i]
    if raw_i64:
        g = (raw_i64[0][perm] if len(raw_i64) == 1 else
             jnp.stack(raw_i64, axis=1)[perm])
        for i in range(len(raw_i64)):
            sorted_cols[("i", i)] = g if len(raw_i64) == 1 else g[:, i]

    # stage 2: masked contributions → batched segment sums
    sum_f64: List[jax.Array] = []
    sum_i64: List[jax.Array] = []

    def _queue_sum(contrib) -> tuple:
        if jnp.issubdtype(contrib.dtype, jnp.floating):
            sum_f64.append(contrib)
            return ("f", len(sum_f64) - 1)
        sum_i64.append(contrib.astype(jnp.int64))
        return ("i", len(sum_i64) - 1)

    pick_first = seg_start & s_active
    pick_last = seg_last

    key_handles = []
    for (d, v), (sd, sv) in zip(keys, s_keys):
        wide = sd.astype(jnp.float64 if jnp.issubdtype(
            sd.dtype, jnp.floating) else jnp.int64)
        h = _queue_sum(jnp.where(pick_first, wide, jnp.zeros_like(wide)))
        vh = _queue_sum((pick_first & sv).astype(jnp.int64)) \
            if sv is not None else None
        key_handles.append((h, vh, d.dtype))

    val_handles: List = []
    for spec in batched_specs:
        if spec[0] == "fallback":
            val_handles.append(spec)
            continue
        _, op, dh, vhraw, orig_dtype = spec
        sd = sorted_cols[dh]
        sv = (sorted_cols[vhraw] > 0) if vhraw is not None else None
        if op == "sum":
            m = s_active if sv is None else (s_active & sv)
            h = _queue_sum(jnp.where(m, sd, jnp.zeros_like(sd)))
            val_handles.append(("batched", h, None, orig_dtype))
        else:
            pick = pick_first if op == "first" else pick_last
            h = _queue_sum(jnp.where(pick, sd, jnp.zeros_like(sd)))
            vh = _queue_sum((pick & sv).astype(jnp.int64)) \
                if sv is not None else None
            val_handles.append(("batched", h, vh, orig_dtype))

    reduced: dict = {}
    # named in the device trace's op metadata (tools/trace_report.py
    # --xplane groups device seconds by it): the kernel family that
    # fills both cells' busy time
    with jax.named_scope("segmented_reduce"):
        if sum_f64:
            out = jax.ops.segment_sum(
                sum_f64[0] if len(sum_f64) == 1 else
                jnp.stack(sum_f64, axis=1), seg_ids, num_segments=capacity)
            for i in range(len(sum_f64)):
                reduced[("f", i)] = out if len(sum_f64) == 1 else out[:, i]
        if sum_i64:
            out = jax.ops.segment_sum(
                sum_i64[0] if len(sum_i64) == 1 else
                jnp.stack(sum_i64, axis=1), seg_ids, num_segments=capacity)
            for i in range(len(sum_i64)):
                reduced[("i", i)] = out if len(sum_i64) == 1 else out[:, i]

    out_keys: List[Value] = []
    for h, vh, orig_dtype in key_handles:
        kd = reduced[h].astype(orig_dtype)
        out_keys.append((kd, reduced[vh] > 0 if vh is not None else None))

    out_vals: List[Value] = []
    for i, spec in enumerate(val_handles):
        if spec[0] == "batched":
            _, h, vh, orig_dtype = spec
            data = reduced[h].astype(orig_dtype)
            out_vals.append(
                (data, reduced[vh] > 0 if vh is not None else None))
        else:
            d, v = contributions[spec[1]][0]
            op = contributions[spec[1]][1]
            sd = d[perm]
            sv = v[perm] if v is not None else None
            out_vals.append(_reduce_segment(sd, sv, op, seg_ids, s_active,
                                            capacity, seg_start, seg_last))
    group_mask = jnp.arange(capacity, dtype=jnp.int32) < n_groups
    return out_keys, out_vals, n_groups, group_mask


def ungrouped_reduce(contributions: List[Tuple[Value, str]], active: jax.Array):
    """Whole-batch (no keys) reduction → one scalar per contribution."""
    outs: List[Value] = []
    for (d, v), op in contributions:
        m = active if v is None else (active & v)
        if op == "sum":
            outs.append((jnp.sum(jnp.where(m, d, jnp.zeros_like(d))), None))
        elif op in ("min", "max"):
            kind = ("f" if jnp.issubdtype(d.dtype, jnp.floating)
                    else "b" if d.dtype == jnp.bool_ else "i")
            sentinel = _SENTINELS[op][kind](d.dtype)
            masked = jnp.where(m, d, jnp.full_like(d, sentinel))
            outs.append(((jnp.min if op == "min" else jnp.max)(masked), None))
        elif op in ("first", "last", "first_valid", "last_valid"):
            # Validity of the partial encodes "this batch had a qualifying
            # row" so the cross-batch merge can skip empty partials (an
            # all-filtered batch must not win the merge with padding data).
            has = jnp.any(m)
            if op in ("first", "first_valid"):
                idx = jnp.argmax(m)
            else:
                idx = d.shape[0] - 1 - jnp.argmax(m[::-1])
            outs.append((jnp.where(has, d[idx], jnp.zeros_like(d[idx])), has))
        else:
            raise ValueError(op)
    return outs


@jax.named_scope("grid_reduce")
def grid_group_reduce(code_keys: List[Value], dims: List[int],
                      contributions: List[Tuple[Value, str]],
                      active: jax.Array):
    """Dense-grid grouped reduction for small-domain integer keys.

    When every group key is a bounded integer code (string dictionary
    codes, booleans), the groups live on a dense grid of
    ``G = prod(dim_i + 1)`` slots (one extra slot per dimension for NULL) —
    so aggregation needs NO sort, NO permutation gather, and no
    boundary machinery: compute a combined grid id per row and run the
    same batched per-dtype ``segment_sum`` passes straight onto G slots,
    then decode observed grid ids back to key columns arithmetically.
    This is the TPU-first shape for low-cardinality GROUP BY (the sort
    path costs a ~100ms lexsort + gathers per 2M-row batch; this path is
    two stacked scatter passes).

    Returns the same contract as :func:`group_reduce`:
    (out_keys, out_vals, n_groups, group_mask), outputs padded to the
    input capacity with observed groups packed at the front (ordered by
    grid id — i.e. by key codes ascending, nulls last per dimension).
    """
    capacity = active.shape[0]
    G = 1
    for d in dims:
        G *= (d + 1)

    gid = jnp.zeros((capacity,), dtype=jnp.int32)
    for (codes, valid), d in zip(code_keys, dims):
        c = codes.astype(jnp.int32)
        slot = jnp.where(valid, c, d) if valid is not None else c
        gid = gid * (d + 1) + slot
    gid = jnp.where(active, gid, G)  # park inactive rows

    # batched per-dtype contribution sums (same trick as group_reduce)
    f64_items: List[jax.Array] = []
    i64_items: List[jax.Array] = []
    handles: List = []
    for (data, valid), op in contributions:
        if op not in ("sum", "first", "last"):
            raise ValueError(f"grid path cannot reduce {op}")
        m = active if valid is None else (active & valid)
        if op == "sum":
            floating = jnp.issubdtype(data.dtype, jnp.floating)
            wide = data.astype(jnp.float64 if floating else jnp.int64)
            contrib = jnp.where(m, wide, jnp.zeros_like(wide))
            if floating:
                f64_items.append(contrib)
                handles.append((("f", len(f64_items) - 1), None, data.dtype))
            else:
                i64_items.append(contrib)
                handles.append((("i", len(i64_items) - 1), None, data.dtype))
        else:
            # first/last on an unsorted grid: pick via segment min/max of
            # row index (rare in practice — buffers are sums)
            n = data.shape[0]
            idx = jnp.arange(n, dtype=jnp.int32)
            cand = jnp.where(m, idx, n if op == "first" else -1)
            f = jax.ops.segment_min if op == "first" else jax.ops.segment_max
            best = f(cand, gid, num_segments=G + 1)
            has = (best < n) if op == "first" else (best >= 0)
            safe = jnp.clip(best, 0, n - 1)
            handles.append((("direct",
                            jnp.where(has[:G], data[safe][:G],
                                      jnp.zeros_like(data[safe][:G])),
                            has[:G]), None, data.dtype))

    reduced: dict = {}
    if G <= 128:
        # MXU path: ONE one-hot f64 dot_general reduces occupancy + every
        # sum column in a single pass over the data.  segment_sum lowers to
        # a scatter that costs ~0.83s per 8M-row stacked pass on this chip;
        # the dot costs ~0.43s for ALL columns (PERF.md lever #4).  int64
        # sums ride exactly as three 22-bit radix chunks in f64 (chunk
        # sums stay under 2^53 for any n < 2^31 rows; the signed top chunk
        # recombines with int64 modular arithmetic, matching int64
        # overflow semantics).
        mats = [jnp.where(active, 1.0, 0.0)]
        spans: List = []
        for i, f in enumerate(f64_items):
            spans.append((("f", i), len(mats), 1))
            mats.append(f)
        mask22 = jnp.int64((1 << 22) - 1)
        for i, x in enumerate(i64_items):
            spans.append((("i", i), len(mats), 3))
            mats.append((x & mask22).astype(jnp.float64))
            mats.append(((x >> 22) & mask22).astype(jnp.float64))
            mats.append((x >> 44).astype(jnp.float64))
        M = mats[0][:, None] if len(mats) == 1 else jnp.stack(mats, axis=1)
        # chunk the row dimension: a whole-batch (n, G) f64 one-hot is
        # n*G*8 bytes of HBM transient (1GB at 8M rows) — scan accumulates
        # the (G, K) result in ~128MB steps instead
        chunk = min(capacity, 1 << 20)
        steps = capacity // chunk
        Mc = M.reshape(steps, chunk, M.shape[1])
        gc_ = gid.reshape(steps, chunk)
        iota_g = jnp.arange(G, dtype=jnp.int32)

        def _step(acc, sl):
            g, m = sl
            oh = (g[:, None] == iota_g[None, :]).astype(jnp.float64)
            return acc + jax.lax.dot_general(
                oh, m, (((0,), (0,)), ((), ()))), None

        out, _ = jax.lax.scan(
            _step, jnp.zeros((G, M.shape[1]), dtype=jnp.float64),
            (gc_, Mc))
        occupancy = out[:, 0]
        observed = occupancy > 0.5
        for key, start, width in spans:
            if width == 1:
                reduced[key] = out[:, start]
            else:
                s0 = out[:, start].astype(jnp.int64)
                s1 = out[:, start + 1].astype(jnp.int64)
                s2 = out[:, start + 2].astype(jnp.int64)
                reduced[key] = s0 + (s1 << 22) + (s2 << 44)
    else:
        if f64_items:
            out = jax.ops.segment_sum(
                f64_items[0] if len(f64_items) == 1 else
                jnp.stack(f64_items, axis=1), gid, num_segments=G + 1)
            for i in range(len(f64_items)):
                reduced[("f", i)] = (out if len(f64_items) == 1
                                     else out[:, i])[:G]
        if i64_items:
            out = jax.ops.segment_sum(
                i64_items[0] if len(i64_items) == 1 else
                jnp.stack(i64_items, axis=1), gid, num_segments=G + 1)
            for i in range(len(i64_items)):
                reduced[("i", i)] = (out if len(i64_items) == 1
                                     else out[:, i])[:G]
        # observed groups: rows contributing to the grid slot
        ones = jnp.where(active, jnp.int32(1), jnp.int32(0))
        occupancy = jax.ops.segment_sum(ones, gid, num_segments=G + 1)[:G]
        observed = occupancy > 0
    n_groups = jnp.sum(observed.astype(jnp.int32))

    # pack observed slots to the front (tiny G-sized argsort)
    pack = jnp.argsort(~observed, stable=True)

    def _pad(x):
        if capacity >= G:
            return jnp.pad(x, [(0, capacity - G)] + [(0, 0)] * (x.ndim - 1))
        return x[:capacity]

    out_vals: List[Value] = []
    for h, _vh, orig_dtype in handles:
        if h[0] == "direct":
            _, data_g, has_g = h
            out_vals.append((_pad(data_g[pack]).astype(orig_dtype),
                             _pad(has_g[pack])))
        else:
            out_vals.append((_pad(reduced[h][pack]).astype(orig_dtype),
                             None))

    # decode grid ids → key code columns (arithmetic, no data pass)
    out_keys: List[Value] = []
    gids_packed = pack.astype(jnp.int32)
    rem = gids_packed
    mults = []
    mult = 1
    for d in reversed(dims):
        mults.append(mult)
        mult *= (d + 1)
    mults = list(reversed(mults))
    for (codes, valid), d, mlt in zip(code_keys, dims, mults):
        slot = (rem // mlt) % (d + 1)
        is_null = slot == d
        out_keys.append((_pad(jnp.where(is_null, 0, slot)).astype(
            codes.dtype), _pad(~is_null)))

    group_mask = jnp.arange(capacity, dtype=jnp.int32) < n_groups
    return out_keys, out_vals, n_groups, group_mask

"""Device-side string handling via dictionary codes.

TPUs have no native string type (SURVEY §7.3); the reference leans on cudf's
device string columns.  Here string *keys* (group-by / join / distinct) are
dictionary-encoded on host into dense int32 codes, the device operates on the
codes (sort, segment-reduce, hash-partition — all int kernels it already
has), and the codes decode back to strings at the output boundary.

The dictionary is INCREMENTAL and query-scoped: every batch that feeds an
operator extends the same mapping, so codes are comparable across batches,
across the partial→exchange→final pipeline, and across the two sides of a
join (both sides encode through one dictionary).  Code order is insertion
order — a valid total order for equality-based operations (group-by, hash
partition, sort-merge equality), NOT for range comparisons or ORDER BY,
which stay on the CPU path.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = ["StringDictionary", "key_codes", "key_view"]


class StringDictionary:
    """Incremental string→int32 code mapping (query-scoped)."""

    _MEMO_MAX = 64

    def __init__(self):
        self._lock = threading.Lock()
        self._code_of: Dict[str, int] = {}
        self._values: List[str] = []
        # memo of already-encoded arrow arrays (keyed by object identity —
        # arrow arrays are immutable and the memo holds the reference, so
        # ids stay valid).  A shuffled join encodes the same staged array
        # in the exchange (for pids) and again in the join kernel.
        self._memo: "Dict[int, tuple]" = {}

    def __len__(self) -> int:
        return len(self._values)

    @classmethod
    def from_arrow(cls, dictionary) -> "StringDictionary":
        """Adopt an arrow dictionary (e.g. a DictStringColumn's) so the
        column's existing int32 codes are valid under this mapping
        verbatim — zero re-encode, zero device round trips."""
        d = cls()
        vals = dictionary.to_pylist()
        d._values = [v for v in vals]
        d._code_of = {v: i for i, v in enumerate(vals) if v is not None}
        d._arrow_src = dictionary
        return d

    def encode(self, arr) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """pyarrow StringArray → (int32 codes, validity-or-None).

        Null slots get code 0 with validity False.
        """
        import pyarrow as pa
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        hit = self._memo.get(id(arr))
        if hit is not None and hit[0] is arr:
            return hit[1], hit[2]
        # per-batch arrow dictionary encode gives local codes fast (C++),
        # then only the (small) local dictionary goes through the python map
        denc = arr.dictionary_encode()
        remap = self._remap(denc.dictionary.to_pylist())
        # null indices read 0: int32 straight out of arrow, never float64
        codes = remap[denc.indices.fill_null(0).to_numpy(
            zero_copy_only=False)]
        valid = np.asarray(arr.is_valid()) if arr.null_count > 0 else None
        with self._lock:
            if len(self._memo) >= self._MEMO_MAX:
                self._memo.clear()
            self._memo[id(arr)] = (arr, codes, valid)
        return codes, valid

    def _remap(self, values) -> np.ndarray:
        """remap[i] = the code of ``values[i]``, new values appended in
        the order given (one lock for all of them)."""
        with self._lock:
            remap = np.zeros(max(len(values), 1), dtype=np.int32)
            for i, v in enumerate(values):
                code = self._code_of.get(v)
                if code is None:
                    code = len(self._values)
                    self._code_of[v] = code
                    self._values.append(v)
                remap[i] = code
        return remap

    def encode_page_codes(self, col) -> Tuple[np.ndarray,
                                              Optional[np.ndarray]]:
        """A ``PageCodedStringColumn`` → (int32 codes over its capacity,
        validity-or-None), hashing no row: each chunk's page dictionary
        goes through the mapping (the values its rows use, in the page's
        order, which a writer fills in order of first occurrence), and its
        indices are gathered through that small map.  Padding and null
        rows read code 0.  The validity is None unless a live row is null:
        the padding lies past ``num_rows``, which every program that reads
        the codes masks."""
        cap = col.capacity
        codes = np.zeros(cap, dtype=np.int32)
        valid = None
        pos = 0
        for chunk in col.chunks:
            n = len(chunk)
            idx = chunk.indices.fill_null(0).to_numpy(zero_copy_only=False)
            live = None
            if chunk.null_count:
                live = np.asarray(chunk.indices.is_valid())
                if valid is None:
                    valid = np.zeros(cap, dtype=bool)
                    valid[:col.num_rows] = True
                valid[pos:pos + n] = live
            used = np.bincount(idx if live is None else idx[live],
                               minlength=len(chunk.dictionary)) > 0
            which = np.flatnonzero(used)
            remap = np.zeros(max(len(chunk.dictionary), 1), dtype=np.int32)
            remap[which] = self._remap(
                chunk.dictionary.take(which).to_pylist())[:len(which)]
            out = codes[pos:pos + n]
            np.take(remap, idx, out=out)
            if live is not None:
                out[~live] = 0
            pos += n
        return codes, valid

    def to_arrow(self):
        """Arrow snapshot of the dictionary values (memoized per size):
        lets operator outputs carry DictStringColumn (device codes +
        this snapshot) instead of eagerly fetching + decoding."""
        import pyarrow as pa
        with self._lock:
            src = getattr(self, "_arrow_src", None)
            if src is not None and len(src) == len(self._values):
                return src
            cached = getattr(self, "_arrow_snap", None)
            if cached is not None and len(cached) == len(self._values):
                return cached
            snap = pa.array(self._values, type=pa.string())
            self._arrow_snap = snap
            return snap

    def decode(self, codes: np.ndarray,
               valid: Optional[np.ndarray] = None):
        """int32 codes → pyarrow StringArray (None where invalid)."""
        import pyarrow as pa
        with self._lock:
            vals = self._values
        out = [None if (valid is not None and not valid[i])
               else vals[int(codes[i])] if 0 <= int(codes[i]) < len(vals)
               else None
               for i in range(len(codes))]
        return pa.array(out, type=pa.string())


# ---------------------------------------------------------------------------------
# String KEY columns of a sort or a window, as int32 codes on the device
# ---------------------------------------------------------------------------------

def _dictionary_ranks(dictionary) -> np.ndarray:
    """rank[i] = position of ``dictionary[i]`` among the dictionary's
    values in binary (UTF-8 byte) order, Spark's string order.  Equal
    values share the smaller position, so a dictionary that repeats a
    value still gives equal strings equal codes."""
    import pyarrow.compute as pc
    order = pc.sort_indices(dictionary).to_numpy(zero_copy_only=False)
    svals = dictionary.take(order)
    n = len(order)
    new = np.ones(n, dtype=bool)
    if n > 1:
        new[1:] = pc.not_equal(svals.slice(1), svals.slice(0, n - 1)) \
            .fill_null(True).to_numpy(zero_copy_only=False)
    first = np.maximum.accumulate(np.where(new, np.arange(n), 0))
    ranks = np.zeros(max(n, 1), dtype=np.int32)
    ranks[order] = first.astype(np.int32)
    return ranks


def key_codes(col, ordered: bool, device=None):
    """A string column as device ``(int32 codes, validity or None)``: equal
    strings get equal codes, and with ``ordered`` a smaller string a
    smaller code (its rank among the column's distinct values).  The codes
    of two columns compare only where both carry one dictionary object, so
    a consumer takes them from ONE batch at a time.

    A ``DictStringColumn`` stays on the device: its codes as they are, or
    a gather through the rank table of its dictionary (made on the host
    and uploaded).  A host column is encoded on the host and uploaded.
    Either way the codes are remembered on the column object.
    """
    import jax.numpy as jnp

    from ..batch import DictStringColumn
    from ..utils.metrics import upload
    cache = col.__dict__.setdefault("_key_codes", {})
    hit = cache.get(ordered)
    if hit is not None:
        return hit
    if isinstance(col, DictStringColumn):
        codes = col.codes
        if ordered:
            table = upload(_dictionary_ranks(col.dictionary), device)
            codes = jnp.take(table, jnp.clip(codes, 0, table.shape[0] - 1))
        hit = _nulls_to_zero(codes, col.valid), col.valid
    else:
        arr = col.array
        denc = arr.dictionary_encode()
        local = denc.indices.fill_null(0).to_numpy(
            zero_copy_only=False).astype(np.int32)
        if ordered:
            local = _dictionary_ranks(denc.dictionary)[local]
        valid = np.asarray(arr.is_valid()) if arr.null_count else None
        hit = upload((_nulls_to_zero(local, valid), valid), device)
    cache[ordered] = hit
    return hit


def _nulls_to_zero(codes, valid):
    """One code for every NULL: the ordering sort compares what lies under
    a NULL too, so NULLs that a CASE's branches made of different rows
    would not tie, and the keys after them would not order them."""
    if valid is None:
        return codes
    import jax.numpy as jnp
    xp = np if isinstance(codes, np.ndarray) else jnp
    return xp.where(valid, codes, xp.zeros_like(codes))


def key_view(batch, ordinals, ordered: bool, device=None):
    """``batch`` with the string columns at ``ordinals`` replaced by device
    columns of :func:`key_codes` (logical STRING, physical int32): what a
    sort's or a window's key expressions evaluate over.  The caller
    gathers the batch it came from, so the strings themselves never move.
    """
    from .. import types as T
    from ..batch import ColumnBatch, DeviceColumn, HostStringColumn
    cols = list(batch.columns)
    for o in ordinals:
        if isinstance(cols[o], HostStringColumn):
            data, valid = key_codes(cols[o], ordered, device)
            cols[o] = DeviceColumn(T.STRING, data, valid)
    return ColumnBatch(batch.schema, cols, batch.num_rows, batch.sel)

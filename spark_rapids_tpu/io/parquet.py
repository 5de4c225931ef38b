"""Parquet scan source with pushdown, row-group pruning, and prefetch.

Reference: GpuParquetScan.scala (2,911 LoC) — host-side footer parse, row-group
clipping by predicate (GpuParquetScan.scala:655-661), host buffer assembly,
then device decode; plus the threaded cloud reader
(GpuMultiFileReader.scala:431) that prefetches files on a CPU pool while the
device computes.  The TPU analog: pyarrow does the host-side parse and decode
into Arrow host memory (there is no TPU parquet decoder and column-major
numeric upload is cheap); this module adds the same three scan optimizations
the reference has:

  * **column pruning** — the planner pushes the plan's referenced-column set
    into the source so unused columns are never decoded or uploaded;
  * **predicate pushdown** — simple comparison conjuncts prune whole row
    groups via parquet footer statistics;
  * **prefetch** — a background thread decodes the next batch while the
    caller uploads/computes the current one (pyarrow parallelizes the column
    decode internally across ``numThreads``).
"""

from __future__ import annotations

import glob as _glob
import os
import queue
import threading
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..batch import (Field, Schema, _arrow_to_logical, bucket_capacity,
                     decimal_view)

__all__ = ["parquet_schema", "parquet_source", "expand_paths", "ParquetSource",
           "prune_row_groups", "Predicate"]

# A pushed-down predicate conjunct: (column, op, value) with op one of
# < <= > >= == != in isnotnull ("in" carries a list value).
Predicate = Tuple[str, str, object]


def expand_paths(path, ext: str = ".parquet") -> List[str]:
    if isinstance(path, (list, tuple)):
        out: List[str] = []
        for p in path:
            out += expand_paths(p, ext)
        return out
    if os.path.isdir(path):
        # recursive: picks up hive-partitioned layouts (p=1/part-....parquet)
        return sorted(_glob.glob(os.path.join(path, "**", f"*{ext}"),
                                 recursive=True))
    if any(ch in path for ch in "*?["):
        return sorted(_glob.glob(path))
    return [path]


def hive_partition_values(root, paths: List[str]):
    """Infer hive-style ``key=value`` partition columns from file paths.

    Returns ``(part_names, {path: {name: raw_string}})``; empty when the
    layout is not partitioned.  Mirrors Spark's partition discovery used by
    the reference's file scans (GpuFileSourceScanExec relies on Spark's
    PartitioningAwareFileIndex).
    """
    if not isinstance(root, str) or not os.path.isdir(root):
        return [], {}
    rootp = os.path.abspath(root)
    names: List[str] = []
    per_path = {}
    for p in paths:
        rel = os.path.relpath(os.path.abspath(p), rootp)
        kv = {}
        for comp in rel.split(os.sep)[:-1]:
            if "=" in comp:
                k, _, v = comp.partition("=")
                # the writer's null sentinel reads back as NULL, like Spark
                kv[k] = None if v == "__HIVE_DEFAULT_PARTITION__" else v
                if k not in names:
                    names.append(k)
        per_path[p] = kv
    if not names:
        return [], {}
    return names, per_path


def _infer_partition_type(values):
    """Narrowest of int64/float64/string fitting every non-null value
    (None = null sentinel or a file outside the partitioned layout)."""
    present = [v for v in values if v is not None]
    if not present:
        return "string"
    try:
        for v in present:
            int(v)
        return "int64"
    except ValueError:
        pass
    try:
        for v in present:
            float(v)
        return "float64"
    except ValueError:
        return "string"


def parquet_schema(paths: List[str], columns: Optional[List[str]] = None) -> Schema:
    import pyarrow.parquet as pq
    pf = pq.ParquetFile(paths[0])
    fields = []
    for f in pf.schema_arrow:
        if columns is None or f.name in columns:
            fields.append(Field(f.name, _arrow_to_logical(f.type), f.nullable))
    if columns is not None:
        order = {n: i for i, n in enumerate(columns)}
        fields.sort(key=lambda f: order[f.name])
    return Schema(fields)


def _stat_keep(stats, op: str, value, num_rows: int) -> bool:
    """Can any row in a row group with these stats satisfy (col op value)?"""
    if op == "isnotnull":
        return stats is None or not getattr(stats, "has_null_count", False) \
            or stats.null_count < num_rows
    if stats is None or not stats.has_min_max:
        return True
    lo, hi = stats.min, stats.max
    try:
        if op == "<":
            return lo < value
        if op == "<=":
            return lo <= value
        if op == ">":
            return hi > value
        if op == ">=":
            return hi >= value
        if op == "==":
            return lo <= value <= hi
        if op == "!=":
            return not (lo == hi == value)
        if op == "in":
            return any(lo <= v <= hi for v in value if v is not None)
    except TypeError:
        return True  # incomparable stat/literal types: cannot prune
    return True


def prune_row_groups(pq_file, predicates: Sequence[Predicate]) -> List[int]:
    """Row-group indices that may contain matching rows
    (GpuParquetScan.scala:655-661 row-group clipping analog)."""
    md = pq_file.metadata
    if not predicates:
        return list(range(md.num_row_groups))
    name_to_idx = {md.schema.column(i).path: i
                   for i in range(md.num_columns)}
    keep: List[int] = []
    for rg in range(md.num_row_groups):
        rgm = md.row_group(rg)
        ok = True
        for name, op, value in predicates:
            ci = name_to_idx.get(name)
            if ci is None:
                continue
            col = rgm.column(ci)
            stats = col.statistics if col.is_stats_set else None
            if not _stat_keep(stats, op, value, rgm.num_rows):
                ok = False
                break
        if ok:
            keep.append(rg)
    return keep


# A PLAIN BYTE_ARRAY value takes its 4-byte length and then its bytes: a
# chunk under this many uncompressed bytes a value is dictionary-coded
_PLAIN_VALUE_MIN_BYTES = 4


def page_coded_columns(pq_file, columns: Optional[List[str]]) -> List[str]:
    """The top-level string columns of ``columns`` (None: all) to read as
    their dictionary pages' codes: every row group's chunk has a
    dictionary page and fewer uncompressed bytes a value than any PLAIN
    value takes.  A chunk whose writer fell back to PLAIN, or a
    near-unique column's, takes more.  Decided from the footer alone."""
    import pyarrow as pa
    md = pq_file.metadata
    index = {md.schema.column(i).path: i for i in range(md.num_columns)}
    out = []
    for f in pq_file.schema_arrow:
        if columns is not None and f.name not in columns:
            continue
        if not pa.types.is_string(f.type):
            continue
        ci = index.get(f.name)
        if ci is None:
            continue
        chunks = [md.row_group(rg).column(ci)
                  for rg in range(md.num_row_groups)]
        if all(c.has_dictionary_page and c.total_uncompressed_size
               < _PLAIN_VALUE_MIN_BYTES * max(c.num_values, 1)
               for c in chunks):
            out.append(f.name)
    return out


def _aligned_tables(pf, pf_coded, coded: List[str],
                    columns: Optional[List[str]], batch_rows: int,
                    rgs: List[int]) -> Iterator:
    """Tables of the batches the plain read makes, whose ``coded`` columns
    are ``pf_coded``'s (opened with ``read_dictionary``) page codes.

    pyarrow ends a dictionary-coded batch where a row group ends, so the
    coded columns come from a reader of their own and their runs are cut
    to the plain columns' batch lengths: a batch may hold the codes of two
    pages, as a ChunkedArray of two chunks."""
    import pyarrow as pa
    names = list(columns) if columns is not None \
        else pf.schema_arrow.names
    plain = [c for c in names if c not in coded]
    runs = pf_coded.iter_batches(batch_size=batch_rows, row_groups=rgs,
                                 columns=[c for c in names if c in coded],
                                 use_threads=True)
    cur, pos = None, 0

    def take(n: int) -> list:
        nonlocal cur, pos
        pieces = []
        while n > 0:
            if cur is None or pos == cur.num_rows:
                cur, pos = next(runs, None), 0
                if cur is None:
                    break
                continue
            k = min(n, cur.num_rows - pos)
            pieces.append(cur.slice(pos, k))
            pos += k
            n -= k
        return pieces

    def table(rb, pieces):
        cols = {name: pa.chunked_array([p.column(name) for p in pieces])
                for name in coded}
        if rb is not None:
            for name in plain:
                cols[name] = rb.column(name)
        return pa.Table.from_arrays([cols[n] for n in names], names=names)

    if plain:
        for rb in pf.iter_batches(batch_size=batch_rows, row_groups=rgs,
                                  columns=plain, use_threads=True):
            yield table(rb, take(rb.num_rows))
        return
    while True:
        pieces = take(batch_rows)
        if not pieces:
            return
        yield table(None, pieces)


_NP_COMPARE = {"<": np.less, "<=": np.less_equal, ">": np.greater,
               ">=": np.greater_equal, "==": np.equal}


def _scaled_compare(col, op: str, value, out: Optional[np.ndarray]):
    """``col <op> value`` over a decimal column of precision <= 18 as a
    compare of its scaled words (:func:`decimal_view`) with the literal at
    the column's scale, ANDed into ``out`` (a numpy mask, or None for a
    new one): Arrow's decimal128 kernels take about twice as long.  NULL
    rows come out False, which the filter drops as it drops NULL.  None
    where this does not apply: another type, or a literal that is not a
    whole number of the column's units."""
    import decimal
    from fractions import Fraction

    import pyarrow as pa
    ty = col.type
    if not (pa.types.is_decimal(ty) and ty.precision <= 18) \
            or isinstance(value, bool) \
            or not isinstance(value, (int, decimal.Decimal)) \
            or (isinstance(value, decimal.Decimal)
                and not value.is_finite()):
        return None
    k = Fraction(value) * 10 ** ty.scale
    if k.denominator != 1 or abs(k) >= 2 ** 63:
        return None
    k = np.int64(k.numerator)
    m = np.empty(len(col), dtype=bool)
    at = 0
    for ch in (col.chunks if isinstance(col, pa.ChunkedArray) else [col]):
        words = decimal_view(ch)
        part = m[at:at + len(ch)]
        _NP_COMPARE[op](words[:, 0] if words.ndim == 2 else words, k,
                        out=part)
        if ch.null_count:
            part &= np.asarray(ch.is_valid())
        at += len(ch)
    if out is not None:
        m &= out
    return m


def _exact_filter_mask(table, predicates: Sequence[Predicate]):
    """Kleene-AND mask of the pushed conjuncts over a decoded host table.

    Applying this before upload is the TPU analog of late materialization:
    selective queries never pay the host→HBM transfer for rows the device
    filter would immediately drop.  Each conjunct mirrors SQL comparison
    semantics (null compares → null → row dropped), matching the device
    filter that still runs downstream, so filtering here is exact, not
    advisory.  Returns None when any conjunct cannot be applied exactly.
    """
    import pyarrow as pa
    import pyarrow.compute as pc
    mask = None
    scaled = None  # the decimal conjuncts' numpy mask (_scaled_compare)
    ops = {"<": pc.less, "<=": pc.less_equal, ">": pc.greater,
           ">=": pc.greater_equal, "==": pc.equal}
    for name, op, value in predicates:
        if name not in table.column_names:
            return None
        col = table[name]
        try:
            if op in ops:
                m = _scaled_compare(col, op, value, scaled)
                if m is not None:
                    scaled = m
                    continue
                m = ops[op](col, value)
            elif op == "in":
                # null list elements only affect non-matching rows (null
                # result), which the filter drops either way
                vals = [v for v in value if v is not None]
                ty = col.type if hasattr(col, "type") else None
                if ty is not None and pa.types.is_dictionary(ty):
                    ty = ty.value_type  # page codes: match their values
                m = pc.is_in(col, value_set=pa.array(vals, type=ty))
            elif op == "isnotnull":
                m = pc.is_valid(col)
            else:
                return None
        except Exception:
            return None  # incomparable literal/column types: skip exact path
        mask = m if mask is None else pc.and_kleene(mask, m)
    if scaled is not None:
        scaled = pa.array(scaled)
        mask = scaled if mask is None else pc.and_kleene(mask, scaled)
    return mask


# A batch's host filter that keeps this share of its rows or more, and
# leaves it in the same capacity bucket, is left to the device filter
# above the scan (pushdown keeps it): the batch uploads the same slots and
# the device does the same work either way, and the copy of every column
# would cost the scan's thread host time for nothing.  A batch under
# _HOST_FILTER_MIN_BYTES is filtered all the same: its copy costs less
# than the sync with which an operator downstream may have to count the
# rows the device filter left.
_HOST_FILTER_MAX_KEEP = 0.9
_HOST_FILTER_MIN_BYTES = 16 << 20


def _filter_pays(table, mask) -> bool:
    """Whether filtering ``table`` by ``mask`` on the host saves the
    device anything, or costs less than it might (above)."""
    import pyarrow.compute as pc
    n = table.num_rows
    kept = pc.sum(mask).as_py() or 0
    if kept < _HOST_FILTER_MAX_KEEP * n \
            or table.nbytes < _HOST_FILTER_MIN_BYTES:
        return True
    strings = any(_arrow_to_logical(ty).is_string
                  for ty in table.schema.types)
    return bucket_capacity(kept, has_strings=strings) \
        != bucket_capacity(n, has_strings=strings)


def _dv_fingerprint(rows) -> tuple:
    """Identity of a deletion vector for cache keys — ONE definition shared
    by the file-cache and device-cache tiers so they can't desynchronize."""
    import zlib
    arr = np.ascontiguousarray(rows)
    return (len(arr), zlib.crc32(arr.tobytes()))


def _anti_fingerprint(names, keys) -> tuple:
    """Identity of one equality-delete group for cache keys (same
    single-definition rule as :func:`_dv_fingerprint`)."""
    import zlib
    return (names, len(keys),
            zlib.crc32(repr(sorted(keys, key=repr)).encode()))


class ParquetSource:
    """A rebuildable parquet scan source.

    The planner calls :meth:`with_pushdown` to narrow columns / attach
    predicates discovered in the plan; calling the instance yields pyarrow
    Tables (the scan exec uploads them).
    """

    fmt = "parquet"

    def __init__(self, path, columns: Optional[List[str]] = None,
                 predicates: Optional[List[Predicate]] = None,
                 batch_rows: int = 1 << 20, num_threads: int = 8,
                 cache_bytes: int = 0, exact_filter: bool = True,
                 _paths: Optional[List[str]] = None,
                 partitions: Optional[tuple] = None,
                 _skip_rows: Optional[dict] = None,
                 _rename: Optional[dict] = None,
                 _anti_rows: Optional[dict] = None):
        self.path = path
        # per-file deleted row indexes (Delta deletion vectors / Iceberg
        # positional deletes): sorted int64 positions into raw row order
        self.skip_rows = _skip_rows or {}
        # per-file equality deletes (Iceberg content=2): path ->
        # [(logical column names, set of deleted value tuples)]
        self.anti_rows = _anti_rows or {}
        # physical (file) name -> logical name (Delta column mapping);
        # self.columns/predicates always speak LOGICAL names
        self.rename = _rename or {}
        self._to_physical = {v: k for k, v in self.rename.items()}
        self.paths = _paths if _paths is not None else expand_paths(path)
        if not self.paths:
            raise FileNotFoundError(f"no parquet files match {path!r}")
        self._partitions = partitions
        if partitions is not None:
            # explicit per-file partition values (Delta log metadata)
            self.part_names, self._part_vals = partitions
        else:
            self.part_names, self._part_vals = hive_partition_values(
                path, self.paths)
        self._part_types = {
            n: _infer_partition_type([self._part_vals[p].get(n)
                                      for p in self.paths])
            for n in self.part_names}
        self._part_nullable = {
            n: any(self._part_vals[p].get(n) is None for p in self.paths)
            for n in self.part_names}
        self.columns = list(columns) if columns is not None else None
        self.predicates = list(predicates or [])
        self.batch_rows = batch_rows
        self.num_threads = num_threads
        self.cache_bytes = cache_bytes
        self.exact_filter = exact_filter

    def schema(self) -> Schema:
        file_cols = None
        if self.columns is not None:
            file_cols = [self._to_physical.get(c, c)
                         for c in self.columns if c not in self.part_names]
        sch = parquet_schema(self.paths, file_cols)
        if self.rename:
            sch = Schema([Field(self.rename.get(f.name, f.name), f.dtype,
                                f.nullable) for f in sch.fields])
        if not self.part_names:
            return sch
        from .. import types as T
        logical = {"int64": T.INT64, "float64": T.FLOAT64, "string": T.STRING}
        fields = list(sch.fields)
        for n in self.part_names:  # Spark appends partition cols at the end
            if self.columns is None or n in self.columns:
                fields.append(Field(n, logical[self._part_types[n]],
                                    self._part_nullable[n]))
        return Schema(fields)

    def with_pushdown(self, columns: Optional[List[str]],
                      predicates: Optional[List[Predicate]]) -> "ParquetSource":
        cols = self.columns
        if columns is not None:
            # preserve file order; never widen beyond the current projection
            base = self.columns if self.columns is not None else \
                self.schema().names()
            cols = [c for c in base if c in set(columns)]
        preds = self.predicates + [p for p in (predicates or [])
                                   if p not in self.predicates]
        return ParquetSource(self.path, cols, preds, self.batch_rows,
                             self.num_threads, self.cache_bytes,
                             self.exact_filter, _paths=self.paths,
                             partitions=self._partitions,
                             _skip_rows=self.skip_rows,
                             _rename=self.rename,
                             _anti_rows=self.anti_rows)

    def estimated_rows(self) -> Optional[int]:
        """Row count from parquet footers minus positional deletes (post
        partition-pruning file list; predicate and equality-delete
        effects not modeled) — the planner's cardinality source
        (CostBasedOptimizer.scala:284 statistics analog).  Memoized per
        source; footer reads are serial, so tables with thousands of
        remote files pay plan-time I/O here once."""
        cached = getattr(self, "_est_rows", False)
        if cached is not False:
            return cached
        try:
            import pyarrow.parquet as pq
            total = 0
            for p in self.paths:
                total += pq.ParquetFile(p).metadata.num_rows
                # positional deletes (Delta DVs / Iceberg) are exact
                total -= len(self.skip_rows.get(p, ()) or ())                     if getattr(self, "skip_rows", None) else 0
        except Exception:
            total = None
        self._est_rows = total
        return total

    def cache_token(self) -> Optional[tuple]:
        """Identity of this scan's output for the device-tier cache: files
        (path+mtime+size), projection, and pushed predicates."""
        files = []
        for p in self.paths:
            try:
                st = os.stat(p)
            except OSError:
                return None
            files.append((os.path.abspath(p), st.st_mtime_ns, st.st_size))
        cols = tuple(self.columns) if self.columns is not None else None
        preds = tuple((n, op, str(v)) for n, op, v in self.predicates)
        dvs = tuple(sorted((p, _dv_fingerprint(r))
                           for p, r in self.skip_rows.items()))
        ren = tuple(sorted(self.rename.items()))
        anti = tuple(sorted(
            (p, tuple(_anti_fingerprint(names, keys)
                      for names, keys in groups))
            for p, groups in self.anti_rows.items()))
        return (tuple(files), cols, preds, self.batch_rows,
                self.exact_filter, dvs, ren, anti)

    def describe(self) -> str:
        d = str(self.path)
        if self.columns is not None:
            d += f" cols={self.columns}"
        if self.predicates:
            d += f" pushdown={[(n, op) for n, op, _ in self.predicates]}"
        return d

    # -- reading ------------------------------------------------------------------
    def _typed_part_value(self, name: str, raw):
        if raw is None:
            return None
        t = self._part_types.get(name, "string")
        if t == "int64":
            return int(raw)
        if t == "float64":
            return float(raw)
        return raw

    def _partition_match(self, path: str, preds) -> bool:
        """File-level partition pruning: skip files whose ``key=value`` path
        components cannot satisfy a pushed conjunct."""
        import operator as _op
        cmp = {"<": _op.lt, "<=": _op.le, ">": _op.gt, ">=": _op.ge,
               "==": _op.eq, "!=": _op.ne}
        kv = self._part_vals.get(path, {})
        for name, op, value in preds:
            if name not in kv:
                continue
            pv = self._typed_part_value(name, kv[name])
            if pv is None:
                # comparison/in with NULL is never true; pushed conjuncts
                # come from real filters, so null-partition files can't match
                return False
            try:
                if op == "in":
                    if pv not in value:
                        return False
                elif op == "isnotnull":
                    continue
                elif op in cmp and not cmp[op](pv, value):
                    return False
            except TypeError:
                continue
        return True

    def _read_file(self, path: str) -> Iterator:
        import pyarrow as pa
        import pyarrow.parquet as pq
        part_kv = self._part_vals.get(path, {})
        file_preds = [p for p in self.predicates
                      if p[0] not in self.part_names]
        if not self._partition_match(path, self.predicates):
            return
        cache = None
        key = None
        if self.cache_bytes > 0:
            from .filecache import FileCache, get_file_cache
            cache = get_file_cache(self.cache_bytes)
        # io.read injection/recovery point: the file open + footer parse
        # is where flaky storage surfaces (EIO, dropped NFS/object-store
        # connections) — transient failures retry with backoff; a
        # missing file is NOT transient and raises straight through.
        # Files this engine's writers published carry a crc sidecar:
        # verify INSIDE the retry scope, so a transiently corrupt read
        # re-reads and a persistently corrupt file exhausts typed.
        from ..faults import integrity
        from ..faults.recovery import transient_retry

        def _verified_open(p=path):
            integrity.verify_file(p)
            return pq.ParquetFile(p)

        pf = transient_retry(None, "io.read", _verified_open, desc=path)
        skips = self.skip_rows.get(path)
        if skips is not None and len(skips) == 0:
            skips = None
        phys_preds = [(self._to_physical.get(n, n), op, v)
                      for n, op, v in file_preds]
        rgs = prune_row_groups(pf, phys_preds)
        pred_key = tuple((n, op, str(v)) for n, op, v in file_preds) \
            if (self.exact_filter and file_preds) else None
        if skips is not None:
            pred_key = (pred_key or ()) + (("dv",) + _dv_fingerprint(skips),)
        anti = self.anti_rows.get(path) or []
        if anti:
            pred_key = (pred_key or ()) + tuple(
                ("anti",) + _anti_fingerprint(names, keys)
                for names, keys in anti)
        # every partition column appears in every file's output (missing in
        # this file's path → null), keeping batch schemas concatenatable
        part_cols = [(n, self._typed_part_value(n, part_kv.get(n)))
                     for n in self.part_names
                     if self.columns is None or n in self.columns]
        file_columns = None if self.columns is None else \
            [self._to_physical.get(c, c)
             for c in self.columns if c not in self.part_names]
        # equality-delete key columns must be decoded even when the query
        # projects them away; they are dropped again after the anti filter
        anti_extra: List[str] = []
        if anti and file_columns is not None:
            projected = set(file_columns)
            for n in sorted({n for names, _ in anti for n in names}):
                pn = self._to_physical.get(n, n)
                if pn not in projected:
                    file_columns.append(pn)
                    anti_extra.append(n)
        if cache is not None:
            from .filecache import FileCache
            key = FileCache.key_for(path, self.columns, rgs)
            if key is not None and pred_key is not None:
                key = key + (pred_key,)
            if key is not None:
                hit = cache.get(key)
                if hit is not None:
                    yield from hit
                    return
        if not rgs:
            return
        acc = [] if (cache is not None and key is not None) else None
        arrow_part = {"int64": pa.int64(), "float64": pa.float64(),
                      "string": pa.string()}
        # low-cardinality string columns stay as their pages' codes
        coded = page_coded_columns(pf, file_columns)
        pf_coded = pq.ParquetFile(path, metadata=pf.metadata,
                                  read_dictionary=coded) if coded else None
        if skips is None and coded and len(rgs) > 1:
            batches = ((t, None) for t in _aligned_tables(
                pf, pf_coded, coded, file_columns, self.batch_rows, rgs))
        elif skips is None:
            # one row group: the coded reader cuts the plain read's batches
            batches = ((pa.Table.from_batches([rb]), None)
                       for rb in (pf_coded or pf).iter_batches(
                           batch_size=self.batch_rows, row_groups=rgs,
                           columns=file_columns, use_threads=True))
        else:
            # one row group at a time: coded batches end where it does
            pf = pf_coded or pf
            # DV positions index the RAW file row order; pruning survives
            # because each kept group's start offset is in the metadata
            group_starts = np.cumsum(
                [0] + [pf.metadata.row_group(g).num_rows
                       for g in range(pf.metadata.num_row_groups)])

            def _dv_batches():
                for g in rgs:
                    off = int(group_starts[g])
                    for rb in pf.iter_batches(
                            batch_size=self.batch_rows, row_groups=[g],
                            columns=file_columns, use_threads=True):
                        yield pa.Table.from_batches([rb]), off
                        off += rb.num_rows
            batches = _dv_batches()
        for t, row_off in batches:
            if skips is not None:
                nrows = t.num_rows
                lo = int(np.searchsorted(skips, row_off))
                hi = int(np.searchsorted(skips, row_off + nrows))
                if hi > lo:
                    mask = np.ones(nrows, dtype=bool)
                    mask[np.asarray(skips[lo:hi]) - row_off] = False
                    t = t.filter(pa.array(mask))
                if t.num_rows == 0:
                    continue
            if self.rename:
                t = t.rename_columns(
                    [self.rename.get(c, c) for c in t.column_names])
            for names, keyset in anti:
                # equality deletes (Iceberg content=2): drop rows whose
                # key tuple appears in the delete set.  Host tuple probe:
                # delete sets are small relative to data (the reference's
                # GpuDeleteFilter builds the same anti-join semantics)
                cols_ = [t.column(n).to_pylist() for n in names]
                keep = [tuple(vals) not in keyset
                        for vals in zip(*cols_)]
                if not all(keep):
                    t = t.filter(pa.array(keep))
            if anti_extra:
                t = t.drop_columns(anti_extra)
            if t.num_rows == 0:
                continue
            for n, v in part_cols:
                ty = arrow_part[self._part_types[n]]
                col = (pa.nulls(t.num_rows, type=ty) if v is None
                       else pa.repeat(pa.scalar(v, type=ty), t.num_rows))
                t = t.append_column(n, col)
            if self.exact_filter and file_preds:
                mask = _exact_filter_mask(t, file_preds)
                if mask is not None and _filter_pays(t, mask):
                    t = t.filter(mask)
                    if t.num_rows == 0:
                        continue
            if acc is not None:
                acc.append(t)
            yield t
        if acc is not None:
            cache.put(key, acc)

    def _read_all(self) -> Iterator:
        for p in self.paths:
            yield from self._read_file(p)

    def __call__(self, prefetch_depth: int = 4) -> Iterator:
        """Yield pyarrow Tables, decoding ahead on a prefetch thread.

        ``prefetch_depth`` bounds the decoded-but-unconsumed tables; the
        scan exec sizes it from ``sql.pipeline.depth`` so the decode pool
        keeps the upload stage fed without pinning unbounded host memory.
        The consumer may abandon the iterator mid-stream (LIMIT, errors);
        a stop event keeps the producer from blocking forever on a full
        queue and leaking the thread + decoded batches.
        """
        if self.num_threads <= 0:
            yield from decoded(self._read_all())
            return
        q: "queue.Queue" = queue.Queue(maxsize=max(1, prefetch_depth))
        stop = threading.Event()
        _END = object()

        def _put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        from ..utils import tracing

        def producer():
            try:
                for t in decoded(self._read_all()):
                    if not _put(t):
                        return
                _put(_END)
            except BaseException as ex:  # propagate to consumer
                _put(ex)

        # the producer runs in a COPY of the caller's context: its spans
        # and stats land in the calling query's trace/scope
        prod = tracing.start_producer(producer, "srt-parquet-prefetch")
        try:
            while True:
                item = next_prefetched(q, prod)
                if item is _END:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()


def decoded(tables: Iterator) -> Iterator:
    """Pass a reader's tables through, each a ``scan:decode`` span on the
    thread that decodes it (the host phase of the scan) whose seconds add
    to ``QueryStats.decode_s``."""
    from ..utils import tracing
    from ..utils.metrics import QueryStats
    while True:
        with tracing.span(None, "scan:decode", "io") as sp:
            t = next(tables, None)
            if t is not None:
                sp.set(rows=t.num_rows)
        QueryStats.get().decode_s += sp.dur
        if t is None:
            return
        yield t


def next_prefetched(q: "queue.Queue", producer):
    """The scan's wait for its prefetch thread (``producer``, from
    ``tracing.start_producer``), as a ``scan:wait`` span."""
    from ..utils import tracing
    with tracing.span(None, "scan:wait", "io", on=producer):
        return q.get()


def parquet_source(path, columns: Optional[List[str]] = None,
                   batch_rows: int = 1 << 20,
                   filters=None) -> Tuple[Schema, Callable[[], Iterator]]:
    """Back-compat helper: returns (schema, factory)."""
    src = ParquetSource(path, columns=columns, batch_rows=batch_rows,
                        predicates=filters)
    return src.schema(), src

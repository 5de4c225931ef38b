"""ORC / JSON / CSV scan sources with column pruning + predicate pushdown.

Reference: GpuOrcScan.scala:74 (ORC scan mirroring the parquet pattern),
GpuJsonScan.scala, GpuCSVScan.scala:205 + GpuTextBasedPartitionReader.scala
(host line framing, device parse).  The TPU shape: pyarrow parses on the
host into Arrow tables (no TPU-side file decoder; numeric column-major
upload is cheap), with the same pushdown contract as
:class:`..io.parquet.ParquetSource` — the planner narrows columns and
attaches predicate conjuncts via :meth:`with_pushdown`, and exact host-side
filtering drops rows before they ever pay the host→HBM transfer.
"""

from __future__ import annotations

import os
import queue
import threading
from typing import Iterator, List, Optional

from ..batch import Field, Schema, _arrow_to_logical, logical_to_arrow
from .parquet import (Predicate, _exact_filter_mask, decoded, expand_paths,
                      next_prefetched)

__all__ = ["FileSource", "OrcSource", "JsonSource", "CsvSource"]


class FileSource:
    """Shared host-parse scan source: per-file load, projection, exact
    filter, fixed-row batch slicing, and background prefetch."""

    fmt = "file"
    ext = ""

    def __init__(self, path, columns: Optional[List[str]] = None,
                 predicates: Optional[List[Predicate]] = None,
                 batch_rows: int = 1 << 20, num_threads: int = 1,
                 _paths: Optional[List[str]] = None, **options):
        self.path = path
        self.paths = _paths if _paths is not None else \
            expand_paths(path, ext=self.ext)
        if not self.paths:
            raise FileNotFoundError(f"no {self.fmt} files match {path!r}")
        self.columns = list(columns) if columns is not None else None
        self.predicates = list(predicates or [])
        self.batch_rows = batch_rows
        self.num_threads = num_threads
        self.options = options

    # -- pushdown contract (same as ParquetSource) --------------------------------
    def schema(self) -> Schema:
        sch = self._file_schema(self.paths[0])
        if self.columns is None:
            return sch
        index = {f.name: f for f in sch}
        return Schema([index[c] for c in self.columns if c in index])

    def with_pushdown(self, columns: Optional[List[str]],
                      predicates: Optional[List[Predicate]]) -> "FileSource":
        cols = self.columns
        if columns is not None:
            base = self.columns if self.columns is not None else \
                self.schema().names()
            cols = [c for c in base if c in set(columns)]
        preds = self.predicates + [p for p in (predicates or [])
                                   if p not in self.predicates]
        return type(self)(self.path, cols, preds, self.batch_rows,
                          self.num_threads, _paths=self.paths,
                          **self.options)

    def describe(self) -> str:
        d = str(self.path)
        if self.columns is not None:
            d += f" cols={self.columns}"
        if self.predicates:
            d += f" pushdown={[(n, op) for n, op, _ in self.predicates]}"
        return d

    def cache_token(self) -> Optional[tuple]:
        """Identity of this scan's output for the cross-query device
        cache — same (files, cols, preds, ...) layout as
        :meth:`..io.parquet.ParquetSource.cache_token` so
        ``cache/keys.scan_key`` composes either source uniformly."""
        files = []
        for p in self.paths:
            try:
                st = os.stat(p)
            except OSError:
                return None
            files.append((os.path.abspath(p), st.st_mtime_ns, st.st_size))
        cols = tuple(self.columns) if self.columns is not None else None
        preds = tuple((n, op, str(v)) for n, op, v in self.predicates)
        opts = tuple(sorted((k, repr(v)) for k, v in self.options.items()))
        return (tuple(files), cols, preds, self.batch_rows, self.fmt,
                opts)

    # -- format hooks -------------------------------------------------------------
    def _file_schema(self, path: str) -> Schema:
        t = self._load_table(path)
        return Schema([Field(n, _arrow_to_logical(ty), True)
                       for n, ty in zip(t.column_names, t.schema.types)])

    def _load_table(self, path: str):  # pragma: no cover - abstract
        raise NotImplementedError

    # -- reading ------------------------------------------------------------------
    def _read_file(self, path: str) -> Iterator:
        # io.read injection/recovery point (same contract as
        # ParquetSource._read_file): the whole-file host parse retries
        # transient storage failures with backoff; files our writers
        # published are crc-verified against their sidecar inside the
        # retry scope
        from ..faults import integrity
        from ..faults.recovery import transient_retry

        def _verified_load(p=path):
            integrity.verify_file(p)
            return self._load_table(p)

        t = transient_retry(None, "io.read", _verified_load, desc=path)
        if self.columns is not None:
            t = t.select([c for c in self.columns if c in t.column_names])
        if self.predicates:
            mask = _exact_filter_mask(t, self.predicates)
            if mask is not None:
                t = t.filter(mask)
        for off in range(0, t.num_rows, self.batch_rows):
            yield t.slice(off, min(self.batch_rows, t.num_rows - off))

    def _read_all(self) -> Iterator:
        for p in self.paths:
            yield from self._read_file(p)

    def __call__(self, prefetch_depth: int = 4) -> Iterator:
        if self.num_threads <= 0 or len(self.paths) <= 1:
            yield from decoded(self._read_all())
            return
        # prefetch next file's decode while the device consumes the
        # current; depth sized by the scan from sql.pipeline.depth
        q: "queue.Queue" = queue.Queue(maxsize=max(1, prefetch_depth))
        stop = threading.Event()
        _END = object()

        from ..utils import tracing

        def producer():
            try:
                for t in decoded(self._read_all()):
                    while not stop.is_set():
                        try:
                            q.put(t, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if stop.is_set():
                        return
                q.put(_END)
            except BaseException as e:  # surfaced on the consumer side
                q.put(e)

        # copied context: decode spans join the calling query's trace
        prod = tracing.start_producer(producer, "srt-source-prefetch")
        try:
            while True:
                item = next_prefetched(q, prod)
                if item is _END:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()


class OrcSource(FileSource):
    fmt = "orc"
    ext = ".orc"

    def _file_schema(self, path: str) -> Schema:
        from pyarrow import orc
        f = orc.ORCFile(path)
        sch = f.schema
        return Schema([Field(n, _arrow_to_logical(ty), True)
                       for n, ty in zip(sch.names, sch.types)])

    def _load_table(self, path: str):
        from pyarrow import orc
        # ORC supports native column projection at read time
        cols = None
        if self.columns is not None:
            names = set(orc.ORCFile(path).schema.names)
            cols = [c for c in self.columns if c in names]
        return orc.ORCFile(path).read(columns=cols)


class JsonSource(FileSource):
    """Line-delimited JSON (Spark's default JSON source shape)."""

    fmt = "json"
    ext = ".json"

    def _load_table(self, path: str):
        import pyarrow.json as pajson
        sch = self.options.get("schema")
        parse = None
        if sch is not None:
            import pyarrow as pa
            parse = pajson.ParseOptions(explicit_schema=pa.schema(
                [(f.name, logical_to_arrow(f.dtype)) for f in sch]))
        return pajson.read_json(path, parse_options=parse)


class CsvSource(FileSource):
    fmt = "csv"
    ext = ".csv"

    def _load_table(self, path: str):
        import pyarrow.csv as pacsv
        header = self.options.get("header", True)
        sep = self.options.get("sep", ",")
        sch = self.options.get("schema")
        read_opts = pacsv.ReadOptions(autogenerate_column_names=not header)
        parse_opts = pacsv.ParseOptions(delimiter=sep)
        convert = None
        kw = {}
        if sch is not None:
            kw["column_types"] = {f.name: logical_to_arrow(f.dtype)
                                  for f in sch}
        if self.columns is not None:
            # projection pushed into the CSV parser itself
            kw["include_columns"] = self.columns
        if kw:
            convert = pacsv.ConvertOptions(**kw)
        return pacsv.read_csv(path, read_options=read_opts,
                              parse_options=parse_opts,
                              convert_options=convert)

    def _file_schema(self, path: str) -> Schema:
        sch = self.options.get("schema")
        if sch is not None and self.columns is None:
            return sch
        t = self._load_table(path)
        return Schema([Field(n, _arrow_to_logical(ty), True)
                       for n, ty in zip(t.column_names, t.schema.types)])

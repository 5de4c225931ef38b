"""TPC-H's ``lineitem`` with its money as the specification types it: the
values ``datagen/tpch.py`` writes at the same seed and scale factor, with
``l_quantity``, ``l_extendedprice``, ``l_discount`` and ``l_tax`` as
DECIMAL(15,2) (dbgen's ``dss.ddl``) in place of float64.

The generator's money is whole hundredths, so each value is its float
times 100, rounded to the integer it stands for: exact.  Parquet holds a
decimal of precision <= 18 as INT64 (``store_decimal_as_integer``), as
Spark writes one; the column is made from the scaled integers' buffer,
with no value going through python.  ``SCHEMA`` gives a money value 8
bytes, the scaled int64 the engine holds on the device.
"""

from __future__ import annotations

import os
from typing import Dict, Iterable, Optional

import numpy as np

from datagen import tpch

TABLES = ["lineitem"]
MONEY = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
PRECISION, SCALE = 15, 2
SCHEMA = {"lineitem": dict(tpch.SCHEMA["lineitem"])}  # money: 8 bytes
rows = tpch.rows


def scaled(values: np.ndarray) -> np.ndarray:
    """Whole hundredths held as float64 -> their int64 count of cents."""
    return np.rint(values * 10 ** SCALE).astype(np.int64)


def decimal_column(cents: np.ndarray):
    """int64 cents -> an arrow decimal128(15,2) array on their buffer."""
    import pyarrow as pa
    words = np.empty((len(cents), 2), dtype=np.int64)
    words[:, 0] = cents
    words[:, 1] = cents >> 63
    return pa.Array.from_buffers(pa.decimal128(PRECISION, SCALE),
                                 len(cents), [None, pa.py_buffer(words)])


def gen(sf: float, seed: int, out_dir: str,
        tables: Optional[Iterable[str]] = None,
        chunk: int = 1_000_000) -> Dict[str, str]:
    """Write ``lineitem`` under ``out_dir``, anew every time, in row groups
    of ``chunk`` rows; returns {table: parquet path}."""
    import pyarrow.parquet as pq

    tables = list(tables) if tables is not None else list(TABLES)
    unknown = sorted(set(tables) - set(TABLES))
    if unknown:
        raise ValueError(f"tpch_decimal datagen has no table {unknown}")
    os.makedirs(out_dir, exist_ok=True)
    _, li = tpch._orders_and_lineitem(seed, tpch.rows(sf))
    for name in MONEY:
        i = li.column_names.index(name)
        cents = scaled(li.column(name).to_numpy())
        li = li.set_column(i, name, decimal_column(cents))
    assert li.column_names == list(SCHEMA["lineitem"])
    path = os.path.join(out_dir, "lineitem.parquet")
    pq.write_table(li, path, row_group_size=chunk,
                   store_decimal_as_integer=True)
    return {"lineitem": path}

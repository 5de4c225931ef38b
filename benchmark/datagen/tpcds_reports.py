"""The TPC-DS ``store_sales`` star with its ``store`` dimension, for the
reporting queries (Q67, Q36, Q89): ``datagen/tpcds.py``'s three tables as
they are, seed for seed the same bytes (its functions write them), plus
``store`` after the specification's (v3) table definition: 29 columns, 12
rows at SF1, the range ``store_sales.ss_store_sk`` already draws from
(``max(2, int(12 * max(sf, 0.1)))``, as ``tpcds._store_sales`` has it).

``store`` stands in for ``dsdgen``'s and is not it (``assumed`` in the
configuration file): ``s_store_id`` is shared by the two revisions of a
store (its slowly changing dimension keeps a business key over revisions,
as ``item`` does here); ``s_store_name`` comes from ``dsdgen``'s syllables,
``s_state`` and ``s_county`` from small pools (``dsdgen`` puts every SF1
store in one county of "TN"; a pool lets Q36's list of eight states pass
some stores and refuse others), ``s_company_name`` is "Unknown" as
``dsdgen`` writes it; the random stream is numpy's; money is float64.
"""

from __future__ import annotations

import os
from typing import Dict, Iterable, Optional

import numpy as np

from datagen import tpcds
from datagen._columns import pick, text

TABLES = sorted(tpcds.TABLES + ["store"])

_I, _F, _D, _S = tpcds._I, tpcds._F, tpcds._D, tpcds._S
SCHEMA = dict(tpcds.SCHEMA)
SCHEMA["store"] = {
    "s_store_sk": _I, "s_store_id": _S, "s_rec_start_date": _D,
    "s_rec_end_date": _D, "s_closed_date_sk": _I, "s_store_name": _S,
    "s_number_employees": _I, "s_floor_space": _I, "s_hours": _S,
    "s_manager": _S, "s_market_id": _I, "s_geography_class": _S,
    "s_market_desc": _S, "s_market_manager": _S, "s_division_id": _I,
    "s_division_name": _S, "s_company_id": _I, "s_company_name": _S,
    "s_street_number": _S, "s_street_name": _S, "s_street_type": _S,
    "s_suite_number": _S, "s_city": _S, "s_county": _S, "s_state": _S,
    "s_zip": _S, "s_country": _S, "s_gmt_offset": _F,
    "s_tax_precentage": _F}   # the specification's spelling

# what the queries' parameters are drawn from
CATEGORIES = tpcds._CATEGORIES
CLASSES = tpcds._CLASSES
STORE_NAMES = ["ought", "able", "pri", "ese", "anti", "cally", "ation",
               "eing", "bar", "n st"]
STATES = ["AL", "GA", "IN", "KS", "KY", "MI", "MN", "MO", "NC", "OH", "SD",
          "TN", "TX", "VA"]
_COUNTIES = ["Williamson County", "Ziebach County", "Walker County",
             "Fairfield County", "Richland County", "Bronx County"]
_HOURS = ["8AM-4PM", "8AM-8AM", "8AM-12AM"]
_STREET_TYPES = ["Ave", "Blvd", "Boulevard", "Circle", "Court", "Ct.",
                 "Dr.", "Drive", "Lane", "Ln", "Parkway", "Pkwy", "RD",
                 "Road", "ST", "Street", "Way", "Wy"]
_CITIES = ["Midway", "Fairview", "Oak Grove", "Five Points", "Pleasant Hill",
           "Riverside", "Centerville", "Mount Pleasant"]


def n_stores(sf: float) -> int:
    return max(2, int(12 * max(sf, 0.1)))


def rows(sf: float) -> Dict[str, int]:
    return {**tpcds.rows(sf), "store": n_stores(sf)}


def _store(rng, n: int):
    import pyarrow as pa
    key = np.arange(1, n + 1, dtype=np.int64)
    start = rng.integers(0, 3, n)
    starts = np.array(["1997-03-13", "2000-03-13", "2001-03-13"],
                      dtype="datetime64[D]")[start]
    ended = rng.random(n) < 0.5
    closed = rng.random(n) < 0.3
    county = rng.integers(0, len(_COUNTIES), n)
    return pa.table({
        "s_store_sk": key,
        "s_store_id": tpcds._business_id((key + 1) // 2),
        "s_rec_start_date": pa.array(starts, type=pa.date32()),
        "s_rec_end_date": pa.array(starts + np.timedelta64(730, "D"),
                                   type=pa.date32(), mask=~ended),
        "s_closed_date_sk": pa.array(
            rng.integers(tpcds._SOLD[0], tpcds._SOLD[1] + 1, n),
            type=pa.int64(), mask=~closed),
        "s_store_name": pick(STORE_NAMES,
                             rng.integers(0, len(STORE_NAMES), n)),
        "s_number_employees": rng.integers(200, 301, n),
        "s_floor_space": rng.integers(5_000_000, 10_000_001, n),
        "s_hours": pick(_HOURS, rng.integers(0, len(_HOURS), n)),
        "s_manager": text(rng, n, 10, 40),
        "s_market_id": rng.integers(1, 11, n),
        "s_geography_class": pick(["Unknown"], np.zeros(n, np.int64)),
        "s_market_desc": text(rng, n, 15, 100),
        "s_market_manager": text(rng, n, 10, 40),
        "s_division_id": np.ones(n, np.int64),
        "s_division_name": pick(["Unknown"], np.zeros(n, np.int64)),
        "s_company_id": np.ones(n, np.int64),
        "s_company_name": pick(["Unknown"], np.zeros(n, np.int64)),
        "s_street_number": pa.array(
            [str(v) for v in rng.integers(1, 1000, n).tolist()]),
        "s_street_name": text(rng, n, 5, 20),
        "s_street_type": pick(_STREET_TYPES,
                              rng.integers(0, len(_STREET_TYPES), n)),
        "s_suite_number": pa.array(
            [f"Suite {v}" for v in rng.integers(0, 500, n).tolist()]),
        "s_city": pick(_CITIES, rng.integers(0, len(_CITIES), n)),
        "s_county": pick(_COUNTIES, county),
        "s_state": pick(STATES, rng.integers(0, len(STATES), n)),
        "s_zip": pa.array(
            [f"{v:05d}" for v in rng.integers(10_000, 99_999, n).tolist()]),
        "s_country": pick(["United States"], np.zeros(n, np.int64)),
        "s_gmt_offset": np.full(n, -5.0),
        "s_tax_precentage": np.round(rng.integers(0, 12, n) / 100.0, 2),
    })


def gen(sf: float, seed: int, out_dir: str,
        tables: Optional[Iterable[str]] = None,
        chunk: int = 1_000_000) -> Dict[str, str]:
    """Write ``tables`` (all four when None) under ``out_dir``; returns
    {table: parquet path}.  The star's three come from ``tpcds.gen``."""
    import pyarrow.parquet as pq

    tables = list(tables) if tables is not None else list(TABLES)
    unknown = sorted(set(tables) - set(TABLES))
    if unknown:
        raise ValueError(f"tpcds_reports datagen has no table {unknown}")
    paths = tpcds.gen(sf, seed, out_dir,
                      [t for t in tables if t != "store"], chunk)
    if "store" in tables:
        os.makedirs(out_dir, exist_ok=True)
        t = _store(tpcds._rng(seed, "store"), n_stores(sf))
        assert t.column_names == list(SCHEMA["store"])
        paths["store"] = os.path.join(out_dir, "store.parquet")
        pq.write_table(t, paths["store"], row_group_size=chunk)
    return paths

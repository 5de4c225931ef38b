"""ops/batch_utils.compact against a numpy oracle, bit for bit.

The oracle is the definition: the live rows (``i < num_rows`` and
``sel[i]``), in order, at the front of ``new_cap`` slots; zeros and
``False`` in every slot past them.  Every form ``_compact_form`` can
choose is run at every shape, whatever the rule would pick there, so a
change of the rule cannot move a shape onto a form no test has seen.
"""

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import (ColumnBatch, DeviceColumn,
                                    DictStringColumn, HostStringColumn,
                                    Schema, bucket_capacity)
from spark_rapids_tpu.ops import batch_utils
from spark_rapids_tpu.plan.physical import PROGRAM_NAMES
from spark_rapids_tpu.utils.metrics import QueryStats

_DTYPES = {"i32": (T.INT32, np.int32), "i64": (T.INT64, np.int64),
           "f64": (T.FLOAT64, np.float64), "bool": (T.BOOLEAN, np.bool_)}


def _data(rng, np_dt, shape):
    if np_dt is np.bool_:
        return rng.random(shape) < 0.5
    if np_dt is np.float64:
        x = rng.standard_normal(shape)
        x.flat[::7] = np.nan        # a NaN must come through as it is
        return x
    return rng.integers(-(1 << 30), 1 << 30, shape).astype(np_dt)


def _oracle(x, active, new_cap):
    """What compact must return for one array: exactly this."""
    idx = np.flatnonzero(active)[:new_cap]
    out = np.zeros((new_cap,) + x.shape[1:], dtype=x.dtype)
    out[:len(idx)] = x[idx]
    return out


def _same(got, want):
    got = np.asarray(got)
    assert got.dtype == want.dtype and got.shape == want.shape
    # bytes, not values: -0.0, NaN payloads and the padding all count
    assert got.tobytes() == want.tobytes()


def _live_mask(rng, cap, num_rows, n_live):
    mask = np.zeros(cap, dtype=bool)
    mask[rng.choice(num_rows, n_live, replace=False)] = True
    mask[num_rows:] = True          # set past num_rows: must stay out
    return mask


@pytest.fixture(params=["rule", "batch_compact", "batch_compact_scatter"])
def form(request, monkeypatch):
    """The rule's own choice, then each form forced at every shape."""
    if request.param != "rule":
        assert request.param in batch_utils.COMPACT_FORMS
        monkeypatch.setattr(batch_utils, "_compact_form",
                            lambda cap, new_cap: request.param)
    return request.param


# (cap, num_rows, n_live, min_capacity): new_cap from the smallest bucket
# up to cap, and past it; n_live 0, 1, all of cap
_SHAPES = [
    (1024, 1000, 0, 1),
    (1024, 1024, 1, 1),
    (1024, 1024, 1024, 1),
    (1024, 700, 300, 4096),         # new_cap > cap (a shared output bucket)
    (16384, 16000, 100, 1),
    (16384, 16384, 9000, 1),
    (262144, 250000, 100, 1),       # the aggregation tails' ratio
    (262144, 262144, 40000, 1),
    (262144, 262144, 200000, 1),    # new_cap == cap, most rows kept
    (262144, 262144, 262144, 1),
]


@pytest.mark.parametrize("n_live_given", [True, False],
                         ids=["n_live_passed", "n_live_fetched"])
@pytest.mark.parametrize("cap,num_rows,n_live,min_cap", _SHAPES)
def test_compact_matches_oracle(form, cap, num_rows, n_live, min_cap,
                                n_live_given):
    rng = np.random.default_rng([cap, n_live, min_cap])
    mask = _live_mask(rng, cap, num_rows, n_live)
    active = mask & (np.arange(cap) < num_rows)
    fields, cols, host = [], [], []
    for name, (ldt, np_dt) in _DTYPES.items():
        for with_valid in (False, True):
            x = _data(rng, np_dt, cap)
            v = rng.random(cap) < 0.8 if with_valid else None
            fields.append((f"{name}_{int(with_valid)}", ldt))
            cols.append(DeviceColumn(
                ldt, jnp.asarray(x), None if v is None else jnp.asarray(v)))
            host.append((x, v))
    x2 = _data(rng, np.float64, (cap, 3))       # a 2-D column
    fields.append(("wide", T.FLOAT64))
    cols.append(DeviceColumn(T.FLOAT64, jnp.asarray(x2), None))
    host.append((x2, None))
    batch = ColumnBatch(Schema.of(*fields), cols, num_rows, jnp.asarray(mask))

    with QueryStats.scoped() as qs:
        out = batch_utils.compact(batch, min_capacity=min_cap,
                                  n_live=n_live if n_live_given else None)
    assert qs.blocking_fetches == (0 if n_live_given else 1)
    new_cap = bucket_capacity(max(n_live, min_cap))
    assert out.num_rows == n_live and out.sel is None
    assert out.capacity == new_cap
    for c, (x, v) in zip(out.columns, host):
        _same(c.data, _oracle(x, active, new_cap))
        if v is None:
            assert c.valid is None
        else:
            _same(c.valid, _oracle(v, active, new_cap))


def test_compact_prefix_only_and_passthrough(form):
    """No selection mask: nothing to do, unless host strings are being
    re-aligned, and then the live rows are the prefix."""
    rng = np.random.default_rng(3)
    cap, num_rows = 4096, 1500
    x = _data(rng, np.int64, cap)
    strings = pa.array([f"s{i}" for i in range(num_rows)], type=pa.string())
    batch = ColumnBatch(
        Schema.of(("x", T.INT64), ("s", T.STRING)),
        [DeviceColumn(T.INT64, jnp.asarray(x)),
         HostStringColumn(strings, capacity=cap)], num_rows)
    assert batch_utils.compact(batch) is batch
    out = batch_utils.compact(batch, align_host_strings=True,
                              n_live=num_rows)
    new_cap = bucket_capacity(num_rows)
    assert (out.num_rows, out.capacity, out.sel) == (num_rows, new_cap, None)
    _same(out.columns[0].data, _oracle(x, np.arange(cap) < num_rows, new_cap))
    assert out.columns[1].array.to_pylist() == \
        strings.to_pylist() + [None] * (new_cap - num_rows)


@pytest.mark.parametrize("n_live_given", [True, False])
def test_compact_string_columns(form, n_live_given):
    """Dictionary codes compact on the device like any column and keep
    their dictionary; a host string column is filtered by the same mask,
    fetched once together with the count."""
    rng = np.random.default_rng(5)
    cap, num_rows, n_live = 16384, 16000, 700
    mask = _live_mask(rng, cap, num_rows, n_live)
    active = mask & (np.arange(cap) < num_rows)
    dictionary = pa.array(["a", "b", "c", "d"], type=pa.string())
    codes = rng.integers(0, 4, cap).astype(np.int32)
    cvalid = rng.random(cap) < 0.9
    strings = pa.array([None if i % 11 == 0 else f"r{i}" for i in range(cap)],
                       type=pa.string())
    x = _data(rng, np.float64, cap)
    batch = ColumnBatch(
        Schema.of(("d", T.STRING), ("h", T.STRING), ("x", T.FLOAT64)),
        [DictStringColumn(jnp.asarray(codes), jnp.asarray(cvalid), dictionary),
         HostStringColumn(strings),
         DeviceColumn(T.FLOAT64, jnp.asarray(x))],
        num_rows, jnp.asarray(mask))
    with QueryStats.scoped() as qs:
        out = batch_utils.compact(batch,
                                  n_live=n_live if n_live_given else None)
    assert qs.blocking_fetches == 1     # the mask, with the count or alone
    new_cap = bucket_capacity(n_live)
    d, h, xc = out.columns
    assert isinstance(d, DictStringColumn) and d.dictionary is dictionary
    _same(d.codes, _oracle(codes, active, new_cap))
    _same(d.valid, _oracle(cvalid, active, new_cap))
    assert type(h) is HostStringColumn
    assert h.array.to_pylist() == \
        [strings[int(i)].as_py() for i in np.flatnonzero(active)] \
        + [None] * (new_cap - n_live)
    _same(xc.data, _oracle(x, active, new_cap))


def test_form_is_a_pure_function_of_its_statics(monkeypatch):
    """The choice reads (cap, new_cap) and nothing else (both forms gather
    every array alike, so the arrays cannot tip it), and every name it
    can return is a program name."""
    assert set(batch_utils.COMPACT_FORMS) <= PROGRAM_NAMES
    grid = [(1 << lc, max(1024, (1 << lc) >> sh))
            for lc in range(10, 25) for sh in (0, 1, 3, 6, 10, 14)]
    first = [batch_utils._compact_form(*g) for g in grid]
    assert set(first) == set(batch_utils.COMPACT_FORMS)
    assert first == [batch_utils._compact_form(*g)
                     for g in reversed(grid)][::-1]
    # more slots never turn a scatter back into a search
    for lc in range(10, 25):
        forms = [batch_utils._compact_form(1 << lc, 1 << ln)
                 for ln in range(10, lc + 1)]
        assert forms == sorted(forms)
    # the measured corners: 100 groups out of a 16.7M-slot table go by
    # search; a filter that keeps most of 4M rows must not
    assert batch_utils._compact_form(1 << 24, 1024) == "batch_compact"
    assert batch_utils._compact_form(1 << 22, 1 << 22) == \
        "batch_compact_scatter"
    # the program that runs is the one the rule named
    rng = np.random.default_rng(9)
    ran = []
    real = batch_utils._compact_program
    monkeypatch.setattr(
        batch_utils, "_compact_program",
        lambda *key: ran.append(real(*key).name) or real(*key))
    for cap, n_live in ((262144, 100), (16384, 16000)):
        x = _data(rng, np.int32, cap)
        mask = _live_mask(rng, cap, cap, n_live)
        batch = ColumnBatch(Schema.of(("x", T.INT32)),
                            [DeviceColumn(T.INT32, jnp.asarray(x))], cap,
                            jnp.asarray(mask))
        out = batch_utils.compact(batch, n_live=n_live)
        new_cap = bucket_capacity(n_live)
        assert ran[-1] == batch_utils._compact_form(cap, new_cap)
        _same(out.columns[0].data, _oracle(x, mask, new_cap))
    assert ran == list(batch_utils.COMPACT_FORMS)

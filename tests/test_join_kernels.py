"""ops/join.py, the sort-merge join's kernels, against a plain numpy loop,
and the two operator libraries that trace them against each other.

The loop is the reference: for every live probe row with no null key, the
live build rows with an equal key.  The kernels may order one key's build
rows as they like, so pairs are compared per probe row as sorted lists.
"""

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.ops import join as J

_N = None  # a null key


def _side(keys, live=None, cap=None):
    """(data, valid, active) padded to ``cap`` rows."""
    n = len(keys)
    cap = cap or n
    data = np.zeros(cap, dtype=np.int64)
    valid = np.zeros(cap, dtype=bool)
    active = np.zeros(cap, dtype=bool)
    for i, k in enumerate(keys):
        if k is not _N:
            data[i], valid[i] = k, True
    active[:n] = True if live is None else live
    return data, valid, active


# name -> (probe keys, probe live mask or None, build keys, build live)
CASES = {
    "dup_keys": ([1, 2, 2, 3, 5, 7, 7], None, [2, 2, 3, 4, 7, 7, 7, 9], None),
    "zero_count_rows": ([10, 1, 11, 12, 2, 13], None, [1, 2, 2, 3], None),
    "all_null_probe": ([_N, _N, _N], None, [1, _N, 2], None),
    "all_null_build": ([1, 2, 2], None, [_N, _N, _N, _N], None),
    "dead_rows": ([1, 2, 2, 3], [True, False, True, True],
                  [2, 2, 3, 3, 1], [True, True, False, True, True]),
    "no_match_at_all": ([1, 2, 3], None, [4, 5, 6, 6], None),
}


def _case(name, cap=8):
    pk, plive, bk, blive = CASES[name]
    return _side(pk, plive, cap), _side(bk, blive, cap)


def _reference_matches(probe, build):
    """Per probe row the sorted build rows it matches."""
    (pd_, pv, pa_), (bd, bv, ba) = probe, build
    return [sorted(j for j in range(len(bd))
                   if pa_[i] and pv[i] and ba[j] and bv[j]
                   and pd_[i] == bd[j])
            for i in range(len(pd_))]


def _match(probe, build):
    (pd_, pv, pa_), (bd, bv, ba) = probe, build
    lo, matches, b_perm = J.match_ranges(
        [(jnp.asarray(pd_), jnp.asarray(pv))],
        [(jnp.asarray(bd), jnp.asarray(bv))],
        jnp.asarray(pa_ & pv), jnp.asarray(ba & bv))
    return lo, matches, b_perm


def _counts(matches, p_active, how):
    c = np.maximum(matches, 1) if how in ("left", "full") else matches
    return np.where(p_active, c, 0).astype(np.int32)


@pytest.mark.parametrize("name", sorted(CASES))
def test_match_ranges(name):
    probe, build = _case(name)
    lo, matches, b_perm = (np.asarray(x) for x in _match(probe, build))
    assert lo.dtype == matches.dtype == b_perm.dtype == np.int32
    assert sorted(b_perm.tolist()) == list(range(len(b_perm)))
    want = _reference_matches(probe, build)
    got = [sorted(b_perm[lo[i]:lo[i] + matches[i]].tolist())
           for i in range(len(lo))]
    assert got == want


def test_match_ranges_two_keys():
    """Both keys must agree, and a null in either keeps the row out."""
    pk = [(1, 1), (1, 2), (2, 1), (1, _N)]
    bk = [(1, 2), (1, 1), (1, 1), (_N, 1), (2, 2)]

    def cols(rows):
        a = _side([r[0] for r in rows], cap=8)
        b = _side([r[1] for r in rows], cap=8)
        return a, b
    (pa0, pa1), (ba0, ba1) = cols(pk), cols(bk)
    p_ok = pa0[2] & pa0[1] & pa1[1]
    b_ok = ba0[2] & ba0[1] & ba1[1]
    lo, matches, b_perm = (np.asarray(x) for x in J.match_ranges(
        [(jnp.asarray(pa0[0]), None), (jnp.asarray(pa1[0]), None)],
        [(jnp.asarray(ba0[0]), None), (jnp.asarray(ba1[0]), None)],
        jnp.asarray(p_ok), jnp.asarray(b_ok)))
    got = [sorted(b_perm[lo[i]:lo[i] + matches[i]].tolist())
           for i in range(4)]
    assert got == [[1, 2], [0], [], []]


@pytest.mark.parametrize("out_cap", [8, 16, 64])
@pytest.mark.parametrize("counts", [
    [2, 0, 3, 1, 0, 0, 1, 0],   # zero-count rows between emitting ones
    [0, 0, 0, 4, 0, 0, 0, 0],   # one row owns every slot
    [0, 0, 0, 0, 0, 0, 0, 0],   # nothing emitted
    [1, 1, 1, 1, 1, 1, 1, 1],
])
def test_expand_rows(counts, out_cap):
    """Slot j belongs to the row i with offsets[i-1] <= j < offsets[i];
    what the slots past the total hold is the caller's to mask."""
    counts = np.asarray(counts, dtype=np.int32)
    offsets = np.cumsum(counts).astype(np.int32)
    total = int(offsets[-1])
    got = np.asarray(J.expand_rows(jnp.asarray(offsets), jnp.asarray(counts),
                                   out_cap))
    want = [i for i, c in enumerate(counts) for _ in range(c)]
    assert got[:total].tolist() == want
    assert got.shape == (out_cap,)
    assert ((got >= 0) & (got < len(counts))).all()


@pytest.mark.parametrize("extra_cap", [0, 24])
@pytest.mark.parametrize("how", ["inner", "left", "full"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_expand_pairs_and_unmatched_build(name, how, extra_cap):
    probe, build = _case(name)
    p_active, b_active = probe[2], build[2]
    lo, matches, b_perm = _match(probe, build)
    counts = _counts(np.asarray(matches), p_active, how)
    offsets = np.cumsum(counts).astype(np.int32)
    total = int(offsets[-1])
    out_cap = max(total, 1) + extra_cap  # the mesh's is larger than total
    pi, bi, matched = (np.asarray(x) for x in J.expand_pairs(
        jnp.asarray(offsets), jnp.asarray(counts), lo, matches, b_perm,
        out_cap))
    ref = _reference_matches(probe, build)
    # the numpy loop: every live probe row's pairs, null-padded if outer
    want = []
    for i in range(len(ref)):
        if not p_active[i]:
            continue
        if ref[i]:
            want += [(i, j) for j in ref[i]]
        elif how in ("left", "full"):
            want.append((i, -1))
    got = sorted(zip(pi[:total].tolist(), bi[:total].tolist()))
    assert got == sorted(want)
    assert pi[:total].tolist() == sorted(pi[:total].tolist())
    assert (matched == (bi >= 0)).all()
    assert not matched[total:].any(), "a slot past the total holds a match"

    un = np.asarray(J.unmatched_build(lo, matches, b_perm,
                                      jnp.asarray(b_active)))
    hit = {j for js in ref for j in js}
    assert un.tolist() == [bool(b_active[j]) and j not in hit
                           for j in range(len(b_active))]


# ---------------------------------------------------------------------------
# the two callers: the mesh's shuffled join (parallel/spmd._Join) and the
# one-chip SortMergeJoinExec give equal rows for one input
# ---------------------------------------------------------------------------

@pytest.fixture()
def shuffle_only(fresh_session):
    sess = fresh_session
    sess.conf.set("spark.rapids.tpu.sql.autoBroadcastJoinThreshold", -1)
    yield sess
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "CACHE_ONLY")
    sess.conf.unset("spark.rapids.tpu.sql.autoBroadcastJoinThreshold")


def _rows(df, sess, mode):
    sess.conf.set("spark.rapids.tpu.shuffle.mode", mode)
    return sorted(df.collect(),
                  key=lambda r: tuple((x is None, x) for x in r))


@pytest.mark.parametrize("right_keys", ["mixed", "all_null"])
@pytest.mark.parametrize("how", ["inner", "left", "right", "full"])
def test_mesh_join_equals_one_chip_join(shuffle_only, how, right_keys):
    """Duplicate keys on both sides, rows that match nothing, null keys,
    and a build side whose every key is null."""
    from spark_rapids_tpu.parallel import spmd
    sess = shuffle_only
    rng = np.random.default_rng(31)
    n_l, n_r = 400, 300
    lk = rng.integers(0, 40, n_l).astype(object)
    lk[rng.random(n_l) < 0.1] = None
    rk = (rng.integers(20, 70, n_r) // 2 * 2).astype(object)  # even keys
    rk[rng.random(n_r) < 0.1] = None
    if right_keys == "all_null":
        rk[:] = None
    left = pa.table({"k": pa.array(lk.tolist(), pa.int64()),
                     "a": pa.array(np.arange(n_l, dtype=np.int64))})
    right = pa.table({"k2": pa.array(rk.tolist(), pa.int32()),
                      "b": pa.array(np.arange(n_r) * 0.5)})
    df = sess.create_dataframe(left).join(
        sess.create_dataframe(right), [("k", "k2")], how)

    emitted = []
    orig = spmd._Join.emit

    def counting(self, env):
        emitted.append(self.how)
        return orig(self, env)
    spmd._Join.emit = counting
    try:
        got = _rows(df, sess, "ICI")
    finally:
        spmd._Join.emit = orig
    assert emitted == [how], "the mesh join did not run"
    want = _rows(df, sess, "CACHE_ONLY")
    assert got == want
    if right_keys == "all_null":
        n_want = {"inner": 0, "left": n_l, "right": n_r, "full": n_l + n_r}
        assert len(got) == n_want[how]

"""Column makers the generators share: text out of one pool of words,
strings picked from a list, money.  Whole columns, vectorised: set-up pays
for every second of data generation in every run."""

from __future__ import annotations

import numpy as np

# clause 4.2.2.13, a part of each list: what the text pool is made of
_WORDS = ("packages requests accounts deposits foxes ideas theodolites "
          "instructions dependencies excuses platelets asymptotes courts "
          "dolphins multipliers sauternes warthogs frets dinos attainments "
          "somas patterns forges braids frays warhorses dugouts notornis "
          "epitaphs pearls tithes waters orbits gifts sheaves depths "
          "sentiments decoys realms pains grouches escapades sleep wake are "
          "cajole haggle nag use boost affix detect integrate maintain nod "
          "was lose sublate solve thrash promise engage hinder print x-ray "
          "breach eat grow impress mold poach serve run dazzle snooze doze "
          "unwind kindle play hang believe doubt furious sly careful blithe "
          "quick fluffy slow quiet ruthless thin close dogged daring brave "
          "stealthy permanent enticing idle busy regular final ironic even "
          "bold silent special pending unusual express sometimes always "
          "never furiously slyly carefully blithely quickly fluffily slowly "
          "quietly ruthlessly thinly closely doggedly daringly bravely "
          "stealthily permanently enticingly idly busily regularly finally "
          "ironically evenly boldly silently about above according across "
          "after against along among around at before behind beneath beside "
          "besides between beyond by despite during except for from inside "
          "instead into near of on outside over past since through "
          "throughout to toward under until up upon without with within"
          ).split()

_POOL_WORDS = 262_144
_pool = None


def _text_pool() -> np.ndarray:
    """About 2 MB of words from the lists, as bytes: the same in every
    process (its stream is not the run's: the seed picks where to read)."""
    global _pool
    if _pool is None:
        rng = np.random.default_rng(4223)
        words = np.array(_WORDS)[rng.integers(0, len(_WORDS), _POOL_WORDS)]
        _pool = np.frombuffer(" ".join(words.tolist()).encode(),
                              dtype=np.uint8)
    return _pool


def text(rng, n: int, lo: int, hi: int):
    """``n`` strings of a length drawn from ``lo..hi`` (clause 4.2.2.10
    gives a text column its range), each the next stretch of the pool read
    round from a place the seed picks: as ``dbgen`` cuts its comments out of
    one long pseudo-text, word boundaries or not."""
    import pyarrow as pa
    pool = _text_pool()
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(rng.integers(lo, hi + 1, n), out=offsets[1:])
    start = int(rng.integers(0, len(pool)))
    total = start + int(offsets[-1])
    data = np.tile(pool, -(-total // len(pool)))[start:total]
    return pa.StringArray.from_buffers(n, pa.py_buffer(offsets),
                                       pa.py_buffer(data))


def pick(values, idx):
    """``values[idx]`` as an arrow string column."""
    import pyarrow as pa
    return pa.array(list(values)).take(pa.array(idx))


def money(rng, lo: float, hi: float, n: int):
    return np.round(rng.uniform(lo, hi, n), 2)



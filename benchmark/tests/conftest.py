"""CPU tests of the harness: ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q`` from the root of the repo."""

import json
import os
import sys

import pytest

# no persistent compile cache on the CPU, as in tests/conftest.py: hashing
# and storing every small program costs more than it saves there
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_SF = 0.01


def make_root(tmp, sf=TINY_SF, extra=None):
    """A throw-away benchmark root: the repo's BENCHMARK.json with every
    configuration cut to ``sf``, its files under ``tmp``.  Nothing of the
    benchmark's own tree is edited: the run finds these files first."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    os.makedirs(os.path.join(tmp, "configs"), exist_ok=True)
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        cfg["sf"] = sf
        c["file"] = f"configs/{c['name']}.json"
        with open(os.path.join(tmp, c["file"]), "w") as f:
            json.dump(cfg, f)
    if extra:
        extra(bench, tmp)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return str(tmp)


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)

"""Bring-up plumbing: the rules that decide whether the program runs on
the chip at all.  The CPU backend is used only when it is named; device
errors are classified by type; the launcher's parent stays off JAX; the
compile cache sits at one place per checkout; chip_smoke.py refuses a
machine without an accelerator; the multichip dryrun names its own
platform."""

import json
import os
import subprocess
import sys
import types

import jax
import pytest

from spark_rapids_tpu.faults.recovery import _is_transient_device
from spark_rapids_tpu.memory.retry import _is_xla_oom
from spark_rapids_tpu.runtime.device import (DeviceManager,
                                             device_memory_bytes)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dev(platform, stats=None):
    return types.SimpleNamespace(platform=platform, device_kind=platform,
                                 memory_stats=lambda: stats)


def _jax(devs, jax_platforms):
    return types.SimpleNamespace(
        devices=lambda *a: list(devs),
        config=types.SimpleNamespace(jax_platforms=jax_platforms))


def test_cpu_only_when_named():
    cpu, tpu = _dev("cpu"), _dev("tpu")
    select = DeviceManager._select_device
    # JAX fell back to the CPU on its own: an error that says what it found
    with pytest.raises(RuntimeError, match="no TPU found.*'cpu'"):
        select(_jax([cpu], None), "")
    assert select(_jax([cpu], "cpu"), "") is cpu      # JAX_PLATFORMS=cpu
    assert select(_jax([cpu], None), "cpu") is cpu    # device.platform=cpu
    assert select(_jax([cpu, tpu], None), "") is tpu


def test_device_memory_is_assumed_on_cpu_only():
    assert device_memory_bytes(_dev("cpu")) == 8 << 30
    assert device_memory_bytes(_dev("tpu", {"bytes_limit": 16 << 30})) \
        == 16 << 30
    with pytest.raises(RuntimeError, match="bytes_limit"):
        device_memory_bytes(_dev("tpu", {}))


def test_real_runtime_errors_are_classified():
    err = jax.errors.JaxRuntimeError
    assert _is_transient_device(err("UNAVAILABLE: socket closed"))
    assert not _is_transient_device(err("RESOURCE_EXHAUSTED: hbm"))
    assert not _is_transient_device(err("INVALID_ARGUMENT: shape"))
    assert _is_xla_oom(err("RESOURCE_EXHAUSTED: Out of memory allocating"))
    assert not _is_xla_oom(err("UNAVAILABLE: socket closed"))
    # what the v5e raised when an output buffer did not fit (PR 21 probe)
    assert _is_xla_oom(ValueError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
        "allocate 4.00G. That was not possible. There are 3.75G free.; "
        "(0x0x0_HBM0)"))
    # the text alone decides nothing: an ordinary Python error is not ours
    assert not _is_xla_oom(ValueError("shape mismatch, out of memory?"))
    assert not _is_transient_device(RuntimeError("UNAVAILABLE"))
    assert not _is_xla_oom(RuntimeError("RESOURCE_EXHAUSTED"))


_CACHE_DIR = """
import jax
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.runtime.warmstore import setup_jax_cache
assert setup_jax_cache(TpuConf())
print(jax.config.jax_compilation_cache_dir)
"""

# dryrun_multichip runs on a virtual CPU mesh and must select it before the
# first backend use, whatever the caller's environment: here JAX_PLATFORMS
# and XLA_FLAGS are unset and no conftest forces the cpu.  If someone
# reorders the platform forcing after a backend use, the platform list
# includes the machine's default platform and this fails.  (The dryrun in a
# process whose cpu backend is already up is
# tests/test_exchange.py::test_dryrun_multichip_entrypoint.)
_DRYRUN = """
import __graft_entry__ as ge
ge.dryrun_multichip(8)
import jax
plats = sorted({d.platform for d in jax.devices()})
assert plats == ["cpu"], f"non-cpu backend initialized: {plats}"
print("PLATFORMS", plats)
"""

_BENCH_PARENT = """
import runpy, sys
code = 0
try:
    runpy.run_path(sys.argv[1], run_name="__main__")
except SystemExit as e:
    code = e.code
print("PARENT_HAS_JAX", "jax" in sys.modules, file=sys.stderr)
sys.exit(code)
"""


@pytest.fixture(scope="module")
def procs(tmp_path_factory):
    """Every subprocess this module needs, started at once: they are
    independent, and tier-1 runs at its time limit."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    bench_env = dict(env, SRT_BENCH_SF="0.001", SRT_BENCH_ITERS="1",
                     SRT_BENCH_QUERIES="q6,q_nope")
    bare_env = {k: v for k, v in env.items()
                if k not in ("JAX_PLATFORMS", "SRT_DRYRUN_ON_DEFAULT")}
    py = sys.executable
    cmds = {
        "cache_a": ([py, "-c", _CACHE_DIR], tmp_path_factory.mktemp("a"),
                    env),
        "cache_b": ([py, "-c", _CACHE_DIR], tmp_path_factory.mktemp("b"),
                    env),
        "smoke": ([py, os.path.join(REPO, "chip_smoke.py")], REPO, env),
        "bench": ([py, "-c", _BENCH_PARENT, os.path.join(REPO, "bench.py")],
                  REPO, bench_env),
        "dryrun": ([py, "-c", _DRYRUN], REPO, bare_env),
    }
    running = {
        name: subprocess.Popen(cmd, cwd=str(cwd), env=e, text=True,
                               stdout=subprocess.PIPE,
                               stderr=subprocess.PIPE)
        for name, (cmd, cwd, e) in cmds.items()}
    done = {}
    for name, p in running.items():
        out, err = p.communicate(timeout=300)
        done[name] = types.SimpleNamespace(rc=p.returncode, out=out, err=err)
    return done


def test_fresh_processes_share_one_cache_inside_the_checkout(procs):
    a, b = procs["cache_a"], procs["cache_b"]
    assert a.rc == 0 and b.rc == 0, (a.err[-2000:], b.err[-2000:])
    assert a.out.strip() == b.out.strip() == \
        os.path.join(REPO, ".cache", "xla")


def test_chip_smoke_refuses_the_cpu(procs):
    p = procs["smoke"]
    assert p.rc not in (0, None), p.err[-2000:]
    assert "platform is cpu" in p.err
    assert p.out.strip() == "", "no accelerator: no result"


def test_chip_smoke_verdict_has_exactly_the_contract_keys(capsys):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    chip_smoke.print_verdict(True, device)
    last = capsys.readouterr().out.splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": device}


def test_dryrun_never_touches_default_backend(procs):
    p = procs["dryrun"]
    assert p.rc == 0, (
        f"dryrun failed in a bare env\nstdout:\n{p.out}\n"
        f"stderr:\n{p.err[-4000:]}")
    assert "dryrun_multichip OK" in p.out
    assert "PLATFORMS ['cpu']" in p.out


def test_bench_parent_stays_off_jax_and_fails_with_its_child(procs):
    p = procs["bench"]
    assert "PARENT_HAS_JAX False" in p.err, p.err[-2000:]
    agg = json.loads(p.out.strip().splitlines()[-1])
    assert agg["queries_completed"] == ["q6"], p.err[-2000:]
    # device identity comes from the child that answered
    assert agg["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert "error" in agg["q_nope"]
    assert p.rc != 0, "a child failed: the run must not exit 0"

"""The reduction from trace events to busy time, idle gaps and labels, on
a hand-made event list."""

import pytest

from harness import trace_reduce as tr

MS = 1_000_000
DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start_ms, dur_ms):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


EVENTS = [
    # the harness's annotations: two queries, 100 ms apart
    ev(HOST, "python", "bench:q3:run", 1000, 400),
    ev(HOST, "python", "bench:q13:run#k=1#", 1500, 500),
    # device operations: overlapping ones count once
    ev(DEV, "XLA Ops", "fusion.1", 1100, 100),
    ev(DEV, "XLA Ops", "fusion.2", 1150, 100),    # busy 1100..1250
    ev(DEV, "XLA Ops", "sort.3", 1350, 50),       # busy 1350..1400
    ev(DEV, "XLA Ops", "fusion.1", 1550, 150),    # busy 1550..1700
    ev(DEV, "XLA Ops", "copy.9", 2100, 50),       # outside the span
    ev(DEV, "XLA Modules", "jit_f(1)", 1100, 900),  # not the ops line
    ev(DEV, "Steps", "0", 0, 5000),
]


def test_busy_idle_and_window():
    r = tr.reduce(EVENTS)
    assert r["window_s"] == pytest.approx(1.0)       # 1000..2000
    assert r["busy_s"] == pytest.approx(0.35)        # 150 + 50 + 150
    assert r["device_idle_pct"] == pytest.approx(65.0)
    assert r["annotated_spans"] == 2


def test_gaps_are_labelled_by_what_the_host_was_doing():
    r = tr.reduce(EVENTS)
    gaps = r["idle_gaps"]
    # 1700..2000 inside q13; 1400..1550 straddles, middle 1475 is between;
    # 1250..1350 and 1000..1100 inside q3
    assert gaps[0] == ["q13:run", pytest.approx(0.3)]
    assert gaps[1] == ["between_queries", pytest.approx(0.15)]
    assert sorted(g[0] for g in gaps[2:]) == ["q3:run", "q3:run"]
    assert sum(g[1] for g in gaps) == pytest.approx(0.65)


def test_top_operations_by_total_time():
    ops = dict(tr.reduce(EVENTS)["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.25)
    assert ops["fusion.2"] == pytest.approx(0.1)
    assert "copy.9" not in ops and "jit_f(1)" not in ops


def test_a_nested_operation_is_not_counted_again_in_its_parent():
    events = [ev(HOST, "python", "bench:q1:run", 0, 150),
              ev(DEV, "XLA Ops", "while.1", 0, 100),
              ev(DEV, "XLA Ops", "fusion.a", 10, 30),
              ev(DEV, "XLA Ops", "while.2", 50, 40),    # nested twice
              ev(DEV, "XLA Ops", "fusion.a", 60, 20),
              ev(DEV, "XLA Ops", "sort.b", 100, 50)]    # follows, not nested
    r = tr.reduce(events)
    ops = dict(r["device_ops"])
    assert ops["while.1"] == pytest.approx(0.03)        # 100 - 30 - 40
    assert ops["while.2"] == pytest.approx(0.02)
    assert ops["fusion.a"] == pytest.approx(0.05)
    assert ops["sort.b"] == pytest.approx(0.05)
    assert sum(ops.values()) == pytest.approx(r["busy_s"])


def test_no_other_line_stands_in_for_the_ops_line():
    assert tr.reduce([e for e in EVENTS if e[1] != "XLA Ops"]) is None


def test_two_devices_average_their_busy_time():
    more = EVENTS + [ev("/device:TPU:1", "XLA Ops", "fusion.1", 1000, 1000)]
    r = tr.reduce(more)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((0.35 + 1.0) / 2)


def test_nothing_on_the_device_is_nothing_to_read():
    host_only = [e for e in EVENTS if e[0] == HOST]
    assert tr.reduce(host_only) is None
    assert tr.reduce([ev("/device:CPU:0", "x", "op", 0, 10)]) is None


def test_without_the_harness_annotations_there_is_no_span():
    assert tr.reduce([e for e in EVENTS if e[0] == DEV]) is None


def test_an_operations_name_is_cut_to_its_head():
    hlo = ("%fusion.6 = (u32[1024]{0:T(1024)S(1)}, u32[1024]{0:T(1024)}) "
           "fusion(u32[16777216]{0:T(1024)} %custom-call.7), kind=kCustom, "
           "calls=%fused_computation.6" + ", %pad" * 40)
    name = tr.short(hlo)
    assert name.startswith("fusion.6 = (u32[1024], u32[1024]) "
                           "fusion(u32[16777216] custom-call.7)")
    assert len(name) <= 160 and name.endswith("...")
    r = tr.reduce([ev(HOST, "python", "bench:q1:run", 0, 10),
                   ev(DEV, "XLA Ops", hlo, 0, 10)])
    assert r["device_ops"][0][0] == name

"""The trace reduction on a hand-built trace: two chips, nested ops,
collectives partly hidden behind compute, and the host's spans."""

import pytest

from benchlib import trace
from benchlib.trace import DeviceOps, Event, Reduced


def ev(name, start, end, collective=False):
    return Event(name, float(start), float(end), collective)


def chip(shift=0):
    s = shift
    ops = [
        ev("while.1", s + 0, s + 40),            # holds the next two
        ev("fusion.2", s + 0, s + 25),
        ev("fusion.3", s + 25, s + 40),
        ev("quantize_op.4", s + 50, s + 60),
        ev("all-reduce.5", s + 60, s + 80, True),
        ev("fusion.6", s + 70, s + 75),            # hides 5 of the 20
        ev("dequant_aggregate_op.7", s + 90, s + 100),
    ]
    return DeviceOps(ops, [ev("all-gather-start.8", s + 100, s + 110, True)])


HOST = [("window", 0.0, 200.0), ("dispatch", 0.0, 5.0),
        ("block", 5.0, 105.0), ("feed", 150.0, 160.0)]


@pytest.fixture
def reduced():
    return Reduced({0: chip(), 1: chip(shift=10)}, HOST)


def test_busy_union_and_window(reduced):
    # ops cover [0, 40] + [50, 80] + [90, 100]: 80 ns on each chip
    assert reduced.busy_s() == pytest.approx(80e-9)
    assert reduced.window_s() == pytest.approx(200e-9)


def test_self_time_by_name(reduced):
    # while.1 has no time of its own; kernels summed by name, mean of chips
    assert reduced.op_seconds(r"while") == 0.0
    assert reduced.op_seconds(r"fusion\.") == pytest.approx(45e-9)
    assert reduced.op_seconds(
        r"(quantize_op|dequant_aggregate_op)(\.\d+)?$") == pytest.approx(20e-9)


def test_collectives_and_exposed(reduced):
    # all-reduce [60, 80] + async all-gather [100, 110] = 30 ns;
    # fusion.6 hides 5 ns of it
    assert reduced.collective_s() == pytest.approx(30e-9)
    assert reduced.exposed_collective_s() == pytest.approx(25e-9)


def test_breakdown_names_ops_and_gaps(reduced):
    b = reduced.breakdown()
    names = [k for k, _ in b["device_ops"]]
    assert names[0] == "fusion"                    # 45 ns, the most
    assert "while" not in names[:3]
    assert dict(b["device_ops"])["all-reduce"] == pytest.approx(15e-9)
    # chip 0's gaps: [40, 50] and [80, 90], both 10 ns, inside 'block'
    # once device time is moved onto the host's clock
    gaps = b["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([10e-9, 10e-9])
    assert all(g[0] == "block" for g in gaps)


def test_interval_helpers():
    assert trace.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.length([(0, 3), (5, 6)]) == 4


def test_instruction_names():
    text = ("%all-reduce-start.3 = (f32[8]{0}) all-reduce-start(f32[8]{0} "
            "%p), replica_groups={{0,1}}")
    assert trace.instr_name(text) == "all-reduce-start.3"
    assert trace.is_collective(text)
    assert not trace.is_collective("%fusion.1 = f32[8]{0} fusion(%all)")
    assert trace.op_kind("dequant_aggregate_op.19") == "dequant_aggregate_op"


def test_needs_a_device_and_one_window():
    with pytest.raises(ValueError):
        Reduced({}, HOST)
    with pytest.raises(ValueError):
        Reduced({0: chip()}, HOST[1:])

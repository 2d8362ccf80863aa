"""The trace reduction on a small trace in its event form, worked by hand.

Chip 0 runs, inside the window [1000, 11000] ns: a fused-CE forward
[1000, 3000], a fusion [2500, 4000], an all-reduce [5000, 7000] with a
fusion [6000, 6500] under it, and a fusion [9000, 12000] that runs past
the window's end; one more fusion lies before the window.  Chip 1 is busy
all through with one fusion, and a `while` [2000, 6000] sits over part of
it (a loop's event holds its body's).  The host is in
`sched.decode_step` over [6800, 9500].
"""

import json
import pathlib

import pytest

from bench import trace_reduce as tr

DATA = pathlib.Path(__file__).parent / "data" / "trace_small.json"


@pytest.fixture(scope="module")
def red():
    raw = json.loads(DATA.read_text())["events"]
    return tr.reduce([tr.Event(*e) for e in raw])


def test_window_and_devices(red):
    assert red["window_s"] == pytest.approx(10000e-9)
    assert red["devices"] == 2


def test_busy_is_the_union_clipped_to_the_window(red):
    # chip 0: [1000,4000] + [5000,7000] + [9000,11000] = 7000; chip 1 10000
    assert red["busy_s"] == pytest.approx((7000 + 10000) / 2 * 1e-9)
    assert red["idle_share"] == pytest.approx(0.15)


def test_kernel_time_by_stable_name(red):
    assert tr.kernel_s(red, "fused_ce_") == pytest.approx(2000e-9 / 2)
    assert tr.kernel_s(red, "sample_topk") == 0.0


def test_exposed_collectives(red):
    assert red["collective_s"] == pytest.approx(2000e-9 / 2)
    # [5000,7000] less the fusion under it [6000,6500]
    assert red["collective_exposed_s"] == pytest.approx(1500e-9 / 2)


def test_breakdown(red):
    names = [n for n, _ in red["device_ops"]]
    # self time: fusion.1 1500 + (10000 - 4000 under the while) ns
    assert names[0] == "fusion.1"
    assert dict(red["device_ops"])["fusion.1"] == pytest.approx(7500e-9 / 2)
    assert dict(red["device_ops"])["while.5"] == pytest.approx(4000e-9 / 2)
    assert dict(red["device_ops"])["fusion.7"] == pytest.approx(2000e-9 / 2)
    assert "fusion.0" not in names                      # before the window
    gaps = red["idle_gaps"]
    assert gaps[0] == ["sched.decode_step", pytest.approx(2000e-9)]
    assert gaps[1] == ["host: no span", pytest.approx(1000e-9)]
    assert len(gaps) == 2


def test_op_name():
    assert tr.op_name("%jvp_fused_ce_fwd_.3 = f32[8]{0} custom-call(%a)") \
        == "jvp_fused_ce_fwd_.3"
    assert tr.op_name("fusion.2") == "fusion.2"


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([tr.Event("/device:TPU:0", "XLA Ops", "f", 0, 1)])

"""The trace reduction on a trace written by hand: busy and idle time,
the step executable's calls and time, op totals, and idle gaps named
after the host span that overlaps them."""
import pytest

from bench import trace


def planes(extra_device=None):
    host = {"name": "/host:CPU", "lines": {"python": [
        ("bench.window", 1000.0, 9000.0),
        ("report", 2000.0, 1000.0),
        ("admit", 6000.0, 500.0),
        ("unrelated", 8100.0, 100.0),
    ]}}
    dev = {"name": "/device:TPU:0", "lines": {
        "XLA Ops": [("fusion.1", 500.0, 1000.0),     # starts before window
                    ("fusion.2", 1400.0, 400.0),     # overlaps the first
                    ("conv", 3500.0, 2000.0),
                    ("fusion.1", 7000.0, 1000.0),
                    ("fusion.3", 9500.0, 1000.0)],   # ends after window
        "XLA Modules": [("jit_step(3)", 500.0, 400.0),
                        ("jit_step(3)", 1000.0, 800.0),
                        ("jit_step(3)", 3500.0, 2000.0),
                        ("jit_other(4)", 7000.0, 1000.0)],
    }}
    custom = {"name": "/device:CUSTOM:Megascale Trace", "lines": {}}
    out = [host, custom, dev]
    if extra_device is not None:
        out.append(extra_device)
    return out


def test_busy_idle_steps_ops_and_gaps():
    r = trace.reduce_trace(planes(), host_spans=("report", "admit"))
    # union of ops clipped to [1000, 10000]:
    # [1000,1800] [3500,5500] [7000,8000] [9500,10000] = 4300 ns
    assert r["window_s"] == pytest.approx(9000e-9)
    assert r["busy_s"] == pytest.approx(4300e-9)
    assert r["step_calls"] == 2
    assert r["step_s"] == pytest.approx(2800e-9)
    assert r["ops"] == [("conv", pytest.approx(2000e-9)),
                        ("fusion.1", pytest.approx(1000e-9)),
                        ("fusion.3", pytest.approx(1000e-9)),
                        ("fusion.2", pytest.approx(400e-9))]
    # gaps [1800,3500] (report), [5500,7000] (admit), [8000,9500] (none)
    assert r["gaps"] == [("report", pytest.approx(1700e-9)),
                         ("admit", pytest.approx(1500e-9)),
                         ("engine host", pytest.approx(1500e-9))]


def test_busy_is_averaged_over_devices():
    other = {"name": "/device:TPU:1",
             "lines": {"XLA Ops": [("fusion.9", 1000.0, 9000.0)]}}
    r = trace.reduce_trace(planes(other))
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((4300e-9 + 9000e-9) / 2)
    assert r["step_calls"] == 1          # two calls on one device of two


def test_union_and_gaps():
    assert trace.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert trace.gaps([(1, 4), (5, 6)], 0, 8) == [(0, 1), (4, 5), (6, 8)]


def test_a_trace_without_window_or_device_is_refused():
    with pytest.raises(ValueError):
        trace.reduce_trace(planes()[1:])
    with pytest.raises(ValueError):
        trace.reduce_trace(planes()[:2])


def test_device_planes():
    assert trace.is_device("/device:TPU:0")
    assert trace.is_device("/device:TPU:3")
    assert not trace.is_device("/device:CUSTOM:Megascale Trace")
    assert not trace.is_device("/device:CPU:0")
    assert not trace.is_device("/host:CPU")

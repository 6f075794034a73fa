"""xplane.py's arithmetic: on hand-made events whose answers are plain, and
on a recorded stretch of a real capture (`data/recorded_trace.json`: half a second
from the middle of the window's capture of one run of the first `adhoc` cell on
the v5e, kept by record_trace.py) against a second way of counting."""

import json
import os

import pytest

from conftest import HERE

import xplane

D, H = "/device:TPU:0", "/host:CPU"


def ev(plane, name, start, dur, line="XLA Ops"):
    return (plane, line, name, float(start), float(dur))


def test_union_gaps_and_names_on_hand_made_events():
    device = [ev(D, "%a.1 = f32[] fusion()", 100, 50),
              ev(D, "%a.2 = f32[] fusion()", 120, 50),     # overlaps a.1
              ev(D, "%copy = u32[] copy()", 400, 100),
              ev(D, "%a.1 = f32[] fusion()", 900, 100)]
    host = [ev(H, "wait", 0, 1000, "t1"),
            ev(H, "parse", 200, 150, "t2"),
            ev(H, "pack", 520, 300, "t2")]
    out = xplane.reduce(device, host)
    assert out["window_s"] == pytest.approx(900e-9)
    assert out["busy_s"] == pytest.approx((70 + 100 + 100) * 1e-9)
    assert out["by_name"] == {"a": pytest.approx(200e-9),
                              "copy": pytest.approx(100e-9)}
    assert out["device_ops"][0][0] == "a"
    gaps = out["idle_gaps"]
    assert [round(g[1] * 1e9) for g in gaps] == [400, 230]
    assert gaps[0][0] == "t2:pack" and gaps[1][0] == "t2:parse"


def test_two_devices_average_and_no_device_reads_nothing():
    device = [ev(D, "x", 0, 100), ev("/device:TPU:1", "x", 0, 300)]
    out = xplane.reduce(device, [ev(H, "h", 0, 400, "t")])
    assert out["device_planes"] == 2
    assert out["busy_s"] == pytest.approx(200e-9)
    assert out["window_s"] == pytest.approx(300e-9)
    assert xplane.reduce([], [ev(H, "h", 0, 400, "t")]) is None


def test_host_frames_do_not_stretch_the_window():
    host = [ev(H, "wait", -9_000_000, 9_001_000, "t1"),
            ev(H, "stop_trace", 3000, 8_000_000, "t3")]
    device = [ev(D, "x", 1000, 500), ev(D, "x", 2500, 100)]
    out = xplane.reduce(device, host)
    assert out["window_s"] == pytest.approx(1600e-9)
    assert sum(g[1] for g in out["idle_gaps"]) == pytest.approx(1000e-9)


def test_recorded_stretch_against_a_sweep():
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        rec = json.load(f)
    device = [tuple(e) for e in rec["device"]]
    host = [tuple(e) for e in rec["host"]]
    assert len(device) > 20 and {e[0] for e in device} == {D}
    out = xplane.reduce(device, host)
    # Busy time again, by counting how many operations are open.
    edges = sorted([(e[3], 1) for e in device]
                   + [(e[3] + e[4], -1) for e in device])
    busy, open_n, since = 0.0, 0, None
    for t, step in edges:
        if open_n == 0 and step == 1:
            since = t
        open_n += step
        if open_n == 0:
            busy += t - since
    assert out["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert sum(out["by_name"].values()) >= out["busy_s"]
    assert 0.4 < out["window_s"] <= 0.5
    assert sum(g[1] for g in xplane.reduce(
        device, host, top=10**6)["idle_gaps"]) \
        == pytest.approx(out["window_s"] - out["busy_s"])
    assert all("=" not in name for name in out["by_name"])

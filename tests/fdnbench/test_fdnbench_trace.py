"""The reduction from a profiler trace to per-layer metrics and the
breakdown, on a hand-built event list and on a trace recorded on one
TPU v5e chip (``recorded/``)."""
import gzip
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from fdnbench import kernels, layers, tracereduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def _batch(t):
    """One admission batch starting at t (ns): admit 200 with children
    snapshot 20, decide 100 (device op 10 inside), enqueue 30; then the
    event loop advances for 100."""
    spans = [(layers.ADMIT, t, 200.0), (layers.SNAPSHOT, t + 10, 20.0),
             (layers.DECIDE, t + 40, 100.0), (layers.ENQUEUE, t + 150, 30.0),
             (layers.ADVANCE, t + 200, 100.0)]
    ops = [("%fusion.1 = f32[4]{0} fusion(f32[4,5]{1,0} %p)", t + 100, 10.0)]
    mods = [("jit_fused_composite_decide(7)", t + 100, 10.0)]
    return spans, ops, mods


def _events(n=4):
    ev = {"spans": [(layers.WINDOW, 0.0, 400.0 * n)], "ops": [],
          "modules": []}
    for k in range(n):
        s, o, m = _batch(400.0 * k)
        ev["spans"] += s
        ev["ops"] += o
        ev["modules"] += m
    return ev


def test_layer_times_self_time_and_idle_attribution():
    s = tracereduce.summarize(_events(4), n_batches=4)
    ms = s["layer_ms"]
    assert ms["admit_self"] == pytest.approx(50e-6)   # 200-20-100-30 ns
    assert ms["snapshot"] == pytest.approx(20e-6)
    assert ms["decide"] == pytest.approx(100e-6)
    assert ms["enqueue"] == pytest.approx(30e-6)
    assert ms["advance"] == pytest.approx(100e-6)
    assert s["window_s"] == pytest.approx(1600e-9)
    assert s["busy_s"] == pytest.approx(40e-9)
    assert s["device_idle_pct"] == pytest.approx(100 * (1 - 40 / 1600))
    gaps = dict(s["breakdown"]["idle_gaps"])
    # per batch: decide 90 idle, admit self 50, advance 100, harness 100
    assert gaps[layers.DECIDE] == pytest.approx(4 * 90e-9)
    assert gaps[layers.ADMIT] == pytest.approx(4 * 50e-9)
    assert gaps[tracereduce.HARNESS] == pytest.approx(4 * 100e-9)
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    assert s["breakdown"]["device_ops"] == [
        ["jit_fused_composite_decide/fusion.1", pytest.approx(4e-8)]]
    assert s["kernel_calls"] == 4 and s["kernel_s"] == pytest.approx(4e-8)


def test_missing_layer_is_none_not_zero():
    ev = _events(2)
    ev["spans"] = [e for e in ev["spans"] if e[0] != layers.SNAPSHOT]
    assert tracereduce.summarize(ev, 2)["layer_ms"]["snapshot"] is None


@pytest.mark.parametrize("drop", ["ops", "window"])
def test_trace_without_device_work_or_window_is_an_error(drop):
    ev = _events(2)
    if drop == "ops":
        ev["ops"] = []
    else:
        ev["spans"] = ev["spans"][1:]
    with pytest.raises(tracereduce.TraceError):
        tracereduce.summarize(ev, 2)


def test_roofline_share_is_per_call_and_unknown_device_fails():
    peak = kernels.peaks("TPU v5 lite")
    share = kernels.roofline_pct([(4, 5)] * 10, 10e-6, 10, peak)
    least = kernels.decide_bytes(4, 5) / peak["hbm_bytes_per_s"]
    assert share == pytest.approx(100 * least / 1e-6)
    assert kernels.roofline_pct([(4, 5)], 0.0, 0, peak) is None
    with pytest.raises(KeyError):
        kernels.peaks("TPU v9 imaginary")


def test_recorded_chip_trace(tmp_path):
    # one second of paper-fdn.poisson-gateway, --trace 1, on one TPU v5
    # lite chip (200 admission windows, 182 of them with arrivals)
    path = tmp_path / "trace.xplane.pb"
    with gzip.open(os.path.join(HERE, "recorded",
                                "paper-fdn.poisson-gateway.xplane.pb.gz")) \
            as src:
        path.write_bytes(src.read())
    ev = tracereduce.load_xplane(str(path))
    s = tracereduce.summarize(ev, n_batches=200)
    assert s["kernel_calls"] == 182
    assert s["window_s"] == pytest.approx(1.004527726)
    assert s["busy_s"] == pytest.approx(0.000487991)
    assert s["kernel_s"] == pytest.approx(0.000636437)
    assert s["layer_ms"] == pytest.approx(
        {"admit_self": 0.1420995, "snapshot": 0.02611948,
         "decide": 3.10523101, "enqueue": 0.108749665,
         "advance": 0.180814945})
    ops = s["breakdown"]["device_ops"]
    assert ops[0] == ["jit_fused_composite_decide/is-finite_reduce_fusion",
                      pytest.approx(0.000148494)]
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert gaps["fdnbench/decide"] == pytest.approx(0.620558211)
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])

"""The benchmark's traffic generator: seeded, every row handed out once,
and every seed given the same work in another order."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from fdnbench.traffic import Traffic, window_key  # noqa: E402

MIXES = ["azure-bulk", "poisson-gateway"]
MMPP = {"loop": "open", "window_s": 0.005, "warmup_sim_s": 120.0,
        "warmup_window_s": 0.25, "rate": {"rps": 100.0},
        "popularity": {"zipf_s": 1.0},
        "arrivals": {"kind": "mmpp", "burst_ratio": 10.0,
                     "quiet_s": [5.2, 2.8], "burst_s": [0.7, 1.3]}}


def _load(kind, name):
    with open(os.path.join(ROOT, "fdnbench", kind, name + ".json")) as fh:
        return json.load(fh)


def _traffic(mix, seed):
    from repro.core.types import FunctionSpec
    config = _load("configs", "paper-fdn")
    specs = [FunctionSpec(name=f["name"]) for f in config["functions"]]
    if isinstance(mix, str):
        mix = _load("traffic", mix)
    return Traffic(mix, config, specs, seed)


def _drain(tr, t_end, w):
    out = []
    for k in range(int(round(t_end / w))):
        b = tr.take((k + 1) * w)
        out.append((b.fn_idx.copy(), b.arrival_t.copy()))
    return out


@pytest.mark.parametrize("mix", MIXES + [MMPP])
def test_same_seed_same_arrivals(mix):
    a = _drain(_traffic(mix, 2 ** 31 + 7), 130.0, 0.5)
    b = _drain(_traffic(mix, 2 ** 31 + 7), 130.0, 0.5)
    for (fa, ta), (fb, tb) in zip(a, b):
        np.testing.assert_array_equal(fa, fb)
        np.testing.assert_array_equal(ta, tb)


@pytest.mark.parametrize("mix", MIXES + [MMPP])
def test_windows_hand_out_every_row_once_in_order(mix):
    tr = _traffic(mix, 5)
    got = _drain(tr, 125.0, 0.25)
    t = np.concatenate([g[1] for g in got])
    assert np.all(np.diff(t) >= 0)
    for k, (_f, tk) in enumerate(got):
        assert np.all(tk >= k * 0.25) and np.all(tk < (k + 1) * 0.25)


def test_mmpp_cycle_is_the_same_work_for_every_seed():
    counts = []
    for seed in (1, 2, 3):
        tr = _traffic(MMPP, seed)
        assert tr._cycle_s == pytest.approx(10.0)
        assert sorted(np.diff(np.concatenate([[0.0], tr._phase_end]))) == \
            pytest.approx(sorted([5.2, 2.8, 0.7, 1.3]))
        n = sum(b.n for b in (tr.take(10.0 * (k + 1)) for k in range(60)))
        counts.append(n)
    mean_rps = tr.rps
    assert tr.burst_rps == pytest.approx(10.0 * tr.base_rps)
    for n in counts:
        assert n == pytest.approx(mean_rps * 600.0, rel=0.05)


def test_azure_minutes_are_the_same_for_every_seed():
    a, b = _traffic("azure-bulk", 1), _traffic("azure-bulk", 2)
    np.testing.assert_array_equal(a._counts, b._counts)
    ta, tb = a.take(60.0), b.take(60.0)
    np.testing.assert_array_equal(np.bincount(ta.fn_idx, minlength=4),
                                  np.bincount(tb.fn_idx, minlength=4))
    assert not np.array_equal(ta.arrival_t, tb.arrival_t)


def test_sustainable_rates_record_the_rehearsal_criterion():
    from fdnbench import rehearse_rate
    config = _load("configs", "paper-fdn")
    assert config["sustainable_criterion"] == rehearse_rate.CRITERION
    for key, mix in config["sustainable_found_with"].items():
        assert key in config["sustainable_rps"]
        assert key == window_key(float(_load("traffic", mix)["window_s"]))


def test_rehearsal_criterion_reads_the_backlog_after_settling():
    from fdnbench import rehearse_rate
    settle = rehearse_rate.CRITERION["settle_s"]
    limit = rehearse_rate.CRITERION["backlog_s"] * 100.0
    start_up = [10 * limit] * settle        # cold starts may queue
    assert rehearse_rate.sustainable(start_up + [limit] * 10, 100.0)
    assert not rehearse_rate.sustainable(start_up + [limit + 1], 100.0)

"""Rate and tail arithmetic of the benchmark's end-to-end metrics."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from fdnbench import stats  # noqa: E402


def test_tail_is_over_all_rows_not_batch_medians():
    # 99 one-row batches at 1 ms and one 100-row batch at 9 ms: half the
    # rows waited 9 ms, so the row median is 9 ms; a median of batch
    # latencies would read 1 ms
    lat = [0.001] * 99 + [0.009]
    rows = [1] * 99 + [100]
    assert stats.row_percentile(lat, rows, 50) == pytest.approx(0.009)
    assert float(np.median(lat)) == pytest.approx(0.001)


@pytest.mark.parametrize("stall_s", [0.010, 0.050, 0.200])
def test_stall_inside_window_moves_the_tail(stall_s):
    # a stall holds 5% of the batches: every row behind it waits longer
    rng = np.random.default_rng(0)
    lat = 0.002 + 0.0005 * rng.random(1000)
    rows = rng.integers(1, 8, 1000)
    base99 = stats.row_percentile(lat, rows, 99)
    stalled = lat.copy()
    stalled[500:550] += stall_s
    assert stats.row_percentile(stalled, rows, 99) >= base99 + 0.9 * stall_s
    assert stats.row_percentile(stalled, rows, 50) == pytest.approx(
        stats.row_percentile(lat, rows, 50), abs=5e-4)


def test_empty_batches_do_not_count():
    assert stats.row_percentile([0.5, 0.001], [0, 3], 99) == \
        pytest.approx(0.001)
    with pytest.raises(ValueError):
        stats.row_percentile([0.1], [0], 50)


@pytest.mark.parametrize("count,seconds,want", [(1000, 2.0, 500.0),
                                                (7, 0.25, 28.0)])
def test_rate_is_work_over_the_whole_window(count, seconds, want):
    assert stats.rate(count, seconds) == pytest.approx(want)


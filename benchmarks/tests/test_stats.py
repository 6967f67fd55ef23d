import pytest

from harness import stats


def test_percentile_interpolates():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([5], 99) == 5
    assert stats.percentile([], 50) is None
    assert stats.percentile(list(range(101)), 99) == 99


def test_gaps_count_only_tokens_inside_the_window():
    streams = [[0.5, 1.0, 1.5, 2.5], [1.2, 1.3]]
    # window [1, 2): stream 0 keeps 1.0, 1.5; stream 1 both
    assert stats.tokens_in_window(streams, 1.0, 2.0) == 4
    gaps = sorted(stats.window_gaps(streams, 1.0, 2.0))
    assert gaps == pytest.approx([0.1, 0.5])
    assert stats.gap_mean(streams, 1.0, 2.0) == pytest.approx(0.3)


def test_a_stalled_stream_shows_in_mean_and_tail():
    steady = [[i * 0.05 for i in range(100)] for _ in range(9)]
    # one stream delivers 50 tokens, stalls 2 s, then 50 more
    stalled = [i * 0.05 for i in range(50)] + [4.45 + i * 0.05 for i in range(50)]
    streams = steady + [stalled]
    gaps = stats.window_gaps(streams, 0.0, 10.0)
    assert len(gaps) == 10 * 99
    assert max(gaps) == pytest.approx(2.0)
    mean = stats.gap_mean(streams, 0.0, 10.0)
    # (9 * 4.95 + 6.9) / 990: the stall is in the mean, not averaged away
    assert mean == pytest.approx((9 * 4.95 + 6.9) / 990)
    # a median of per-stream means would hide it; the pooled p99.95 does not
    assert stats.percentile(gaps, 99.95) > 1.0


def test_clumps():
    # tokens arrive three at a time, every 0.3 s
    s = [k * 0.3 + j * 1e-4 for k in range(10) for j in range(3)]
    assert stats.clump_tokens([s], 0.0, 10.0) == pytest.approx(3.0)
    assert stats.clump_tokens([[0.1, 0.2, 0.3]], 0.0, 1.0) == 1.0
    assert stats.clump_tokens([[]], 0.0, 1.0) is None

"""Latency statistics: TTFT from the due time, tails over all requests."""
import numpy as np
import pytest

from bench import stats as S


def test_ttft_is_measured_from_the_due_time_not_the_submit_time():
    # submitted 300 ms late by a starved generator: the wait counts
    recs = [{"due": 10.0, "submit_t": 10.3, "token_t": [10.5, 10.6]}]
    assert S.ttft_samples(recs, 99.0) == [pytest.approx(0.5)]


def test_a_request_without_a_first_token_counts_as_a_miss():
    recs = [{"due": 1.0, "token_t": [1.1]}, {"due": 2.0, "token_t": []}]
    assert S.ttft_samples(recs, 50.0) == [pytest.approx(0.1), pytest.approx(48.0)]


def test_tails_are_taken_over_all_requests_not_per_chunk():
    # two halves: one fast, one slow. The median of per-half p95s would
    # hide the slow half's share; the p95 of all samples does not.
    fast = [{"due": 0.0, "token_t": [0.01 * (1 + i % 5)]} for i in range(100)]
    slow = [{"due": 0.0, "token_t": [1.0 + 0.01 * i]} for i in range(10)]
    allr = fast + slow
    p95 = S.percentile(S.ttft_samples(allr, 9.0), 95)
    assert p95 == pytest.approx(np.percentile(
        [r["token_t"][0] for r in allr], 95))
    assert p95 > 0.5


def test_inter_token_gaps_cover_every_request():
    recs = [{"due": 0, "token_t": [0.0, 0.1, 0.3]}, {"due": 0, "token_t": [1.0, 1.5]},
            {"due": 0, "token_t": [2.0]}]
    assert S.itl_samples(recs) == pytest.approx([0.1, 0.2, 0.5])

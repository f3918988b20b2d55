"""End-to-end arithmetic: unserved requests count at their elapsed wait."""
import numpy as np
import pytest

import _paths  # noqa: F401
from harness import stats
from harness.stats import Track


def _tracks():
    return [
        Track(uid=0, due=10.0, max_new=3, admit=10.5,
              tokens=[11.0, 11.2, 11.5]),
        Track(uid=1, due=12.0, max_new=4, admit=13.0,
              tokens=[14.0, 14.1]),                 # still decoding
        Track(uid=2, due=15.0, max_new=2),   # never admitted
        Track(uid=3, due=16.0, max_new=2, admit=19.5,
              tokens=[21.0, 21.5]),                 # first token after close
        Track(uid=4, due=9.0, max_new=1, tokens=[9.5]),
    ]


def test_due_in_window_only():
    due = stats.due_in(_tracks(), 10.0, 20.0)
    assert [t.uid for t in due] == [0, 1, 2, 3]


def test_ttft_counts_unserved_at_elapsed_wait():
    due = stats.due_in(_tracks(), 10.0, 20.0)
    waits = stats.waits_until(due, 20.0, "first")
    assert waits == pytest.approx([1.0, 2.0, 5.0, 4.0])
    assert stats.percentile(waits, 90) == pytest.approx(
        float(np.percentile([1.0, 2.0, 5.0, 4.0], 90)))


def test_queue_wait_counts_unadmitted_at_elapsed_wait():
    due = stats.due_in(_tracks(), 10.0, 20.0)
    assert stats.waits_until(due, 20.0, "admit") == pytest.approx(
        [0.5, 1.0, 5.0, 3.5])


def test_inter_token_gaps_stop_at_the_close():
    gaps = stats.inter_token_gaps(_tracks(), 20.0)
    assert sorted(gaps) == pytest.approx(sorted([0.2, 0.3, 0.1]))


def test_tokens_in_window():
    assert stats.tokens_in(_tracks(), 10.0, 20.0) == 5


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 90) is None


def test_done():
    t = _tracks()
    assert t[0].done and not t[1].done and not t[2].done

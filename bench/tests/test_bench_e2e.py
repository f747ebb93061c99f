"""bench/e2e.py: whole-window statistics from the harness's records,
against counts made by hand."""
import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import e2e
from bench.serve_loop import Record


def rec(due, submit, tokens):
    r = Record(prompt=[1], max_new=len(tokens), due=due, submit_t=submit)
    r.token_t = list(tokens)
    return r


RECS = [rec(9.0, 9.0, [9.5, 10.5, 11.0]),      # due before the window
        rec(10.0, 10.1, [10.4, 10.6, 10.9]),
        rec(12.0, 12.2, [12.5, 20.5]),           # last token after close
        rec(19.9, 19.95, [])]                    # no token yet


def test_window_membership_and_ttft():
    assert len(e2e.in_window(RECS, 10.0, 20.0)) == 3
    assert e2e.ttfts_ms(RECS, 10.0, 20.0) == pytest.approx([400.0, 500.0])


def test_gaps_end_inside_the_window():
    # (10.5-9.5), (11.0-10.5), (10.6-10.4), (10.9-10.6); 20.5 is past close
    assert e2e.itls_ms(RECS, 10.0, 20.0) == pytest.approx(
        [1000.0, 500.0, 200.0, 300.0])


def test_compute_named_metrics():
    got = e2e.compute(["setup_s", "tokens_per_s"], RECS, 10.0, 20.0, 42.0)
    assert got == {"setup_s": 42.0, "tokens_per_s": pytest.approx(6 / 10.0)}


def test_closed_loop_uses_submit_time():
    r = rec(None, 15.0, [15.25])
    assert e2e.ttfts_ms([r], 10.0, 20.0) == pytest.approx([250.0])

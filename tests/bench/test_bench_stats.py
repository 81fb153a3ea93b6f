"""bench/stats and the end-to-end readers, on hand-made timestamps."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, stats  # noqa: E402
from bench.records import Req, Run  # noqa: E402


def test_percentile_interpolates_like_numpy():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 90) == pytest.approx(46.0)
    assert stats.percentile(xs, 0) == 10.0 and stats.percentile(xs, 100) == 50.0
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_token_gaps_and_union():
    assert stats.token_gaps([1.0, 1.5, 1.75]) == [0.5, 0.25]
    assert stats.token_gaps([1.0]) == []
    assert stats.interval_union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]


def _run(reqs, t0=0.0, t1=10.0):
    return Run(cell=types.SimpleNamespace(), hf={}, peak={}, t0=t0, t1=t1,
               requests=reqs, extra={"drain_allowance_s": 30.0})


def _reader(name):
    return cells.load_module(ROOT, "metrics", name).read


def test_ttft_counts_from_the_due_time_and_a_failure_is_a_miss():
    reqs = [Req(t_due=1.0, t_sent=1.2, n_prompt=8, max_new=2,
                stamps=[1.5, 1.6], done=True),  # 0.5 s from DUE, not sent
            Req(t_due=None, t_sent=2.0, n_prompt=8, max_new=2,
                stamps=[2.25, 2.3], done=True),  # closed loop: from the send
            Req(t_due=3.0, t_sent=3.0, n_prompt=8, max_new=2, stamps=[3.1],
                failed=True)]  # failed: waits the window plus the allowance
    assert stats.ttft_values(reqs, 40.0) == [0.5, 0.25, 40.0]
    assert _reader("ttft_ms_p90")(_run(reqs)) == pytest.approx(
        stats.percentile([500.0, 250.0, 40000.0], 90))


def test_itl_pools_gaps_over_requests_and_rate_counts_the_window():
    reqs = [Req(None, 0.0, 4, 3, stamps=[1.0, 1.1, 1.4], done=True),
            Req(None, 0.0, 4, 3, stamps=[9.0, 9.9, 10.5], done=True)]
    assert sorted(stats.pooled_gaps(reqs)) == pytest.approx(
        [0.1, 0.3, 0.6, 0.9])
    assert _reader("itl_ms_p95")(_run(reqs)) == pytest.approx(
        stats.percentile([100.0, 300.0, 600.0, 900.0], 95))
    # five tokens inside [0, 10), the sixth arrived after the window
    assert _reader("output_tokens_per_s")(_run(reqs)) == pytest.approx(0.5)


def test_generate_reader():
    reqs = [Req(None, 0.0, 4, 2, stamps=[2.0], done=True),
            Req(None, 2.0, 4, 2, stamps=[5.0], done=True),
            Req(None, 5.0, 4, 2, stamps=[9.0], done=True)]
    assert _reader("generate_ms_p50")(_run(reqs)) == pytest.approx(3000.0)


def test_span_readers_use_spans_inside_the_window_only():
    def span(name, ts, dur, **args):
        return {"name": name, "ph": "X", "ts": int(ts * 1e6),
                "dur": int(dur * 1e6), "args": args}

    run = _run([])
    run.spans = [span("decode_step", 1.0, 0.01, occupancy=8, slots=32),
                 span("decode_step", 1.02, 0.03, occupancy=24, slots=32),
                 span("decode_step", 1.30, 0.02, occupancy=16, slots=32),
                 span("decode_step", 11.0, 9.0, occupancy=32, slots=32),
                 span("prefill", 1.06, 0.2), span("prefill", 2.0, 0.4)]
    assert _reader("step.decode_ms_p50")(run) == pytest.approx(20.0)
    assert _reader("step.prefill_ms_p50")(run) == pytest.approx(300.0)
    assert _reader("engine.decode_occupancy")(run) == pytest.approx(50.0)
    # gaps: 1.01 -> 1.02 and 1.05 -> 1.30
    assert _reader("engine.host_gap_ms_p50")(run) == pytest.approx(130.0)
    assert _reader("engine.host_gap_ms_p95")(run) == pytest.approx(238.0)
    assert _reader("step.decode_ms_p50")(_run([])) is None

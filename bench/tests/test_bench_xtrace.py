"""The trace reduction, on a small trace recorded on a TPU v5e by
bench/tools/record_trace_fixture.py: three rounds of a matmul chain under
``bench.train_step``, then 30 ms idle under ``bench.data`` and 20 ms idle
under no annotation, all inside ``bench.window``."""
from pathlib import Path

import pytest

from bench.lib import xtrace

FIXTURE = Path(__file__).parent / "data" / "fixture.xplane.pb"


def test_union_and_clip():
    assert xtrace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert xtrace.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


@pytest.fixture(scope="module")
def summary():
    return xtrace.summarize(FIXTURE)


def test_busy_union_and_idle_share(summary):
    assert summary.devices == 1
    assert 0.15 < summary.window_s < 0.4
    assert 0 < summary.busy_s < summary.window_s
    idle = xtrace.idle_percent(summary)
    assert idle == pytest.approx(
        100 * (1 - summary.busy_s / summary.window_s))
    # the pauses alone are 150 ms of the window
    assert sum(summary.idle_by_host.values()) >= 0.14
    assert sum(summary.idle_by_host.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-9)


def test_gaps_are_attributed_to_the_host_span_open_in_them(summary):
    assert summary.idle_by_host["bench.data"] == pytest.approx(0.09, abs=0.01)
    assert summary.idle_by_host["host.unannotated"] >= 0.055
    # each pause is one gap of ~50 ms, named after its larger share
    top = summary.longest_gaps[:3]
    assert [n for n, _ in top] == ["bench.data"] * 3
    assert all(0.045 < t < 0.06 for _, t in top)
    assert summary.longest_gaps[3][1] < 0.01
    b = summary.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][1] == summary.longest_gaps[0][1]


def test_module_times(summary):
    ms = xtrace.module_mean_ms(summary, ("jit_",))
    assert ms is not None and ms > 0
    assert xtrace.module_mean_ms(summary, ("no-such-program",)) is None

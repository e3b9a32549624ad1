"""The trace reduction, on a short trace recorded on one v5e chip
(``data/sdar8b-serve.xplane.pb``: a traced run of ``sdar8b-sharegpt``):
device busy time as the union of the operations, idle gaps named by the
host span open during them, the kernel's time, the programs the host
waited for."""

from pathlib import Path

import jax
import pytest

from bench import trace as TR

DATA = Path(__file__).parent / "data" / "sdar8b-serve.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return TR.reduce_trace(DATA)


@pytest.fixture(scope="module")
def raw():
    return jax.profiler.ProfileData.from_file(str(DATA))


def _window(raw):
    for plane in raw.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == TR.WINDOW_SPAN:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    raise AssertionError("no window span")


def test_busy_is_the_union_of_device_operations(summary, raw):
    lo, hi = _window(raw)
    plane = next(p for p in raw.planes if TR.DEVICE_PLANE.match(p.name))
    ops = sorted((max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
                 for line in plane.lines if line.name == TR.OPS_LINE
                 for e in line.events
                 if e.start_ns < hi and e.start_ns + e.duration_ns > lo)
    busy, end = 0.0, lo
    for s, e in ops:                 # sweep: count each instant once
        if e > end:
            busy += e - max(s, end)
            end = e
    assert summary.busy_s == pytest.approx(busy / 1e9, rel=1e-9)
    assert summary.window_s == pytest.approx((hi - lo) / 1e9)
    assert 0 < summary.busy_s < summary.window_s


def test_idle_gaps_are_named_by_host_spans_and_add_up(summary):
    idle = sum(summary.idle_by_span.values())
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)
    names = set(summary.idle_by_span)
    assert names and all(n.startswith("bench.") or n == TR.OUTSIDE
                         for n in names)
    top = summary.top_idle()
    assert len(top) <= 10 and [v for _, v in top] == sorted(
        (v for _, v in top), reverse=True)


def test_programs_are_known_by_the_span_that_waited(summary):
    secs, n = summary.module_stats("bench.backend.decode_dispatch")
    marks = summary.marks["bench.mark.tick"]["count"]
    assert n >= 1 and abs(n - marks) <= 1
    assert 0 < secs < summary.busy_s


def test_kernel_time_and_breakdown(summary):
    kernel = summary.op_seconds(r'custom_call_target="tpu_custom_call"')
    assert 0 < kernel < summary.busy_s
    ops = summary.top_ops()
    assert len(ops) <= 10 and any(k.startswith("tpu_custom_call")
                                  for k, _ in ops)
    assert not any(k.startswith("while") for k, _ in ops)


def test_union_and_innermost_by_hand():
    assert TR._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]
    spans = sorted([(0, 10, "outer"), (2, 5, "inner"), (6, 8, "other")])
    assert TR._innermost(spans, 3)[2] == "inner"
    assert TR._innermost(spans, 5.5)[2] == "outer"
    assert TR._innermost(spans, 11) is None

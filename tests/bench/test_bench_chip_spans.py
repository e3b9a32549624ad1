"""The serving path's spans in a trace recorded on one v5e chip
(``data/sdar8b-spans.xplane.pb``: a traced run of ``sdar8b-sharegpt``
with the spans, its window cut to half a second): the device's idle time
inside ticks falls under phase spans, the readings the span reduction
gives, the programs' names, and the accepted reduction still reading it.
The per-layer readers that read the spans give those readings, and the
paged-attention kernel is known by its name on this trace and on the one
recorded before the spans existed (``data/sdar8b-serve.xplane.pb``)."""

import dataclasses
from pathlib import Path

import pytest

from bench import harness as H
from bench import spans as SP
from bench import spec as S
from bench import trace as TR

DATA = Path(__file__).parent / "data" / "sdar8b-spans.xplane.pb"
OLD_TRACE = Path(__file__).parent / "data" / "sdar8b-serve.xplane.pb"
# what the run that recorded the trace read (11 ticks, one prefill launch
# of 36 prompt tokens in 64 slots)
LEAF_SHARE = 0.9598491369229328
TICK_HOST_MS = 5.171451363636363
PREFILL_PAD_PCT = 43.75
ACCEPTED = {"decode_step_ms": 32.84143972727273,
            "prefill_ms_per_ktok": 746.2393333333334,
            "paged_attn_roofline": 2.537318204443357,
            "device_idle_pct": 16.494539166324795}


@pytest.fixture(scope="module")
def spans():
    return SP.read_spans(DATA)


@pytest.fixture(scope="module")
def summary():
    return TR.reduce_trace(DATA)


def test_idle_split_adds_up_to_the_devices_idle_time(spans, summary):
    assert sum(spans.idle_by_phase.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)
    assert 0 < spans.idle_in_ticks_s <= summary.window_s - summary.busy_s


def test_phases_hold_the_idle_time_inside_ticks(spans):
    assert spans.leaf_idle_share() >= 0.9
    assert spans.leaf_idle_share() == pytest.approx(LEAF_SHARE, rel=1e-6)


def test_readings(spans):
    assert spans.tick_host_ms() == pytest.approx(TICK_HOST_MS, rel=1e-6)
    assert spans.prefill_pad_pct() == pytest.approx(PREFILL_PAD_PCT,
                                                    rel=1e-6)


def test_programs_are_named(summary):
    names = {m.name.split("(")[0] for m in summary.modules}
    assert {"jit_decode_step_paged", "jit_prefill_chunk_paged"} <= names
    assert "jit__unknown" not in names
    kernel = [o.name for o in summary.ops
              if 'custom_call_target="tpu_custom_call"' in o.name]
    assert kernel and all(k.startswith("%chunked_paged_attn") for k in kernel)


def test_accepted_metrics_read_the_new_trace(summary):
    """The accepted readers read the trace with the spans as the run that
    recorded it reported."""
    run = H.RunData({}, None, [], [], summary)
    assert {m: S.metric_reader(m)(run) for m in ACCEPTED} == pytest.approx(
        ACCEPTED, rel=1e-9)


@pytest.mark.parametrize("trace, want", [(DATA, TICK_HOST_MS),
                                         (OLD_TRACE, None)],
                         ids=["spans", "before-spans"])
def test_tick_host_ms_reader(trace, want):
    """The metric reads the span reduction's host time per tick from the
    run's spans, and nothing from a trace without tick spans."""
    run = H.RunData({}, None, [], [], None, spans=SP.read_spans(trace))
    got = S.metric_reader("tick_host_ms")(run)
    assert got == (None if want is None else pytest.approx(want, rel=1e-9))
    assert S.metric_reader("tick_host_ms")(H.RunData({}, None, [], [],
                                                     None)) is None


# a second Pallas kernel beside the paged one, as a routed-expert layer's
# grouped matmul would be
OTHER_KERNEL = ("%grouped_matmul.3 = bf16[64,8,768]{2,1,0:T(8,128)(2,1)} "
                "custom-call(bf16[64,2048]{1,0} %p.1, bf16[16,2048,768]"
                "{2,1,0} %p.2), custom_call_target=\"tpu_custom_call\", "
                "frontend_attributes={kernel_metadata={}}")


@pytest.mark.parametrize("trace", [DATA, OLD_TRACE],
                         ids=["spans", "before-spans"])
def test_paged_kernel_is_known_by_its_name(trace):
    """The paged kernel's op counts by its name (named, or left unnamed by
    a program that names none); another Pallas kernel's op in the same
    window leaves ``paged_attn_roofline`` and the other accepted readings
    as they are."""
    summary = TR.reduce_trace(trace)
    run = H.RunData({}, None, [], [], summary)
    before = {m: S.metric_reader(m)(run) for m in ACCEPTED}
    if trace == DATA:
        assert before == pytest.approx(ACCEPTED, rel=1e-9)
    kernel = [o for o in summary.ops if o.name.startswith(("%chunked",
                                                           "%closed"))]
    assert kernel
    other = TR.Op(OTHER_KERNEL, kernel[0].start, kernel[0].dur)
    more = dataclasses.replace(summary, ops=summary.ops + [other])
    # the op is in the window, and a pattern on the call's target alone
    # would count it
    target = r'custom_call_target="tpu_custom_call"'
    assert more.op_seconds(target) == pytest.approx(
        summary.op_seconds(target) + other.dur / 1e9)
    run = H.RunData({}, None, [], [], more)
    assert {m: S.metric_reader(m)(run) for m in ACCEPTED} == before

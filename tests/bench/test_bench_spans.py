"""The serving path's own spans (``serving.*``) in a profiler trace, and
their reduction (``bench/spans.py``).

A short ``EngineCore`` + ``ModelBackend`` run at the configuration's
smoke size, with the CPU rehearsal's sizes, is traced on the CPU: every
span of the serving path appears, each phase nests in its backend step
and its engine tick, the ``tick`` stats are the engine's steps, and the
reduction sums them.  On the trace recorded on the chip before the spans
existed, the reduction finds nothing and the accepted metrics read as
they did."""

import time
from pathlib import Path

import jax
import pytest

from bench import harness as H
from bench import spans as SP
from bench import spec as S
from bench import trace as TR

CELL = "sdar8b-sharegpt"
OLD_TRACE = Path(__file__).parent / "data" / "sdar8b-serve.xplane.pb"
ENGINE = ("serving.engine.admit", "serving.scheduler.select",
          "serving.engine.capacity", "serving.engine.commit")
BACKEND = ("serving.backend.prefill.plan", "serving.backend.prefill.pack",
           "serving.backend.prefill.launch", "serving.backend.prefill.wait",
           "serving.backend.prefill.commit", "serving.backend.decode.reserve",
           "serving.backend.decode.assemble", "serving.backend.decode.pack",
           "serving.backend.decode.launch", "serving.backend.decode.wait",
           "serving.backend.decode.commit", "serving.backend.decode.trim")
# (prompt, output) tokens: prompts longer than one prefill chunk continue
# over ticks while short ones start beside them
LENGTHS = ((40, 20), (9, 12), (33, 6), (17, 20), (12, 9), (40, 15))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(span summary, engine core, recorder) of a traced CPU run."""
    from repro.launch.serve import init_model, model_serving
    from repro.models.common import ArchConfig
    from repro.serving import DATASETS
    from repro.serving.engine import EngineCore
    from repro.serving.request import Request

    cell = S.resolve_cell(CELL)
    spec = S.model_spec(cell.config, smoke=True)
    model, params = init_model(ArchConfig(**cell.model.arch_kwargs(spec)),
                               5)
    reqs = [Request(i, 0.0, p, o,
                    prompt_tokens=list(range(4 + 50 * i, 4 + 50 * i + p)))
            for i, (p, o) in enumerate(LENGTHS)]
    backend, sched = model_serving(model, params, reqs, "elastic",
                                   DATASETS["sharegpt"], seed=5,
                                   attn_impl="kernel")
    clock = H.WallClock()
    rec = H.Recorder(backend, clock, spec)
    core = EngineCore(backend, sched, max_batch=H.REHEARSAL["max_batch"],
                      clock=clock, tracer=rec)
    core.submit_all(reqs)
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        while core.tick():
            pass
    finally:
        jax.profiler.stop_trace()
    summary = SP.read_spans(next(out.rglob("*.xplane.pb")))
    return summary, core, rec


def test_every_span_appears(traced):
    summary, core, _ = traced
    names = summary.by_name()
    for name in (SP.TICK, SP.STEP) + ENGINE + BACKEND:
        assert names.get(name, {}).get("count", 0) >= 1, name
    assert names[SP.TICK]["count"] == core._steps
    assert names["serving.engine.admit"]["admitted"] == len(LENGTHS)
    assert names["serving.engine.commit"]["finished"] == len(LENGTHS)
    assert summary.idle_by_phase is None       # no device plane on the CPU


def test_phases_nest_in_their_step_and_tick(traced):
    summary, _, _ = traced
    by_tick = {}
    for sp in summary.spans:
        by_tick.setdefault(sp.stats["tick"], []).append(sp)

    def inside(sp, name, group):
        return [o for o in group if o.name == name
                and o.start <= sp.start and sp.end <= o.end]

    for tick, group in by_tick.items():
        assert len([s for s in group if s.name == SP.TICK]) == 1, tick
        for sp in group:
            assert inside(sp, SP.TICK, group), sp
            if sp.name.startswith("serving.backend.") and sp.name != SP.STEP:
                assert inside(sp, SP.STEP, group), sp


def test_tick_ids_are_the_engines_steps(traced):
    summary, core, rec = traced
    ticks = [sp.stats["tick"] for sp in summary.spans if sp.name == SP.TICK]
    assert ticks == list(range(1, core._steps + 1))
    steps = [sp.stats["tick"] for sp in summary.spans if sp.name == SP.STEP]
    assert len(steps) == len(rec.ticks) and set(steps) <= set(ticks)


def test_reduction_sums_the_spans(traced):
    summary, _, rec = traced
    names = summary.by_name()
    launches = [sp for sp in summary.spans
                if sp.name == "serving.backend.decode.launch"]
    assert names["serving.backend.decode.launch"]["valid"] == sum(
        sp.stats["valid"] for sp in launches)
    assert names["serving.backend.decode.commit"]["committed"] == sum(
        t.n_committed for t in rec.ticks)
    secs = sum(sp.end - sp.start for sp in summary.spans
               if sp.name == SP.TICK) / 1e9
    assert names[SP.TICK]["seconds"] == pytest.approx(secs)
    assert 0 < summary.tick_host_ms() < 1e3 * secs / names[SP.TICK]["count"]


def test_prefill_padding_agrees_with_the_plan(traced):
    """The launch spans' token counts, and the per-layer metric that reads
    the padded shape off each tick's plan, give one number."""
    summary, _, rec = traced
    pad = summary.prefill_pad_pct()
    assert 0 < pad < 100
    read = S.metric_reader("prefill_pad_pct")
    assert read(H.RunData({}, None, [], rec.ticks, None)) == pytest.approx(pad)
    tokens = summary.by_name()["serving.backend.prefill.launch"]["tokens"]
    assert tokens == sum(p for p, _ in LENGTHS)


def test_metric_reads_none_on_a_program_without_padded_plans():
    ticks = [H.Tick(0.0, plan=[(0, 0, 16), (1, 0, 9)])]
    read = S.metric_reader("prefill_pad_pct")
    assert read(H.RunData({}, None, [], ticks, None)) is None


def test_old_trace_has_no_spans_and_reads_as_before():
    """The accepted per-layer metrics read from a trace read exactly what
    they read before the program had spans; the span reduction finds
    nothing in that trace."""
    summary = SP.read_spans(OLD_TRACE)
    assert summary.spans == [] and summary.by_name() == {}
    assert summary.tick_host_ms() is None
    assert summary.prefill_pad_pct() is None
    assert summary.leaf_idle_share() is None
    run = H.RunData({}, None, [], [], TR.reduce_trace(OLD_TRACE))
    readings = {m: S.metric_reader(m)(run) for m in (
        "decode_step_ms", "prefill_ms_per_ktok", "paged_attn_roofline",
        "device_idle_pct")}
    assert readings == pytest.approx({
        "decode_step_ms": 58.3270745,
        "prefill_ms_per_ktok": 865.233375,
        "paged_attn_roofline": 2.3847450251771884,
        "device_idle_pct": 13.34410464385497}, rel=1e-9)


def test_untraced_run_gives_readers_no_spans(monkeypatch):
    """``RunData.spans`` is None on an untraced run."""
    made, cls = [], H.RunData

    def run_data(*a, **k):
        made.append(cls(*a, **k))
        return made[-1]
    monkeypatch.setattr(H, "RunData", run_data)
    res = H.run_cell(S.resolve_cell(CELL), 2**31 + 29, 3.0, False,
                     t_start=time.perf_counter(), rehearse=True,
                     log=lambda s: None)
    assert res["checks_pass"] is True
    assert len(made) == 1
    assert made[0].spans is None and made[0].trace is None


def test_innermost_pieces_and_split_by_hand():
    spans = [SP.Span("tick", 0, 10, {}), SP.Span("a", 1, 3, {}),
             SP.Span("b", 5, 7, {}), SP.Span("c", 6, 9, {})]
    pieces = SP._innermost_pieces(spans)
    assert pieces == [(0, 1, "tick"), (1, 3, "a"), (3, 5, "tick"),
                      (5, 6, "b"), (6, 7, "c"), (7, 10, "tick")]
    split = SP._split([(2, 6), (9, 12)], pieces)
    assert split == pytest.approx({"a": 1e-9, "tick": 3e-9, "b": 1e-9,
                                   TR.OUTSIDE: 2e-9})

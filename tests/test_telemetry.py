"""Telemetry layer tests: null-tracer determinism, tick/decision logging,
scheduler-decision replay (ISSUE acceptance), lifecycle spans, Perfetto
export validity, ring-buffer bounds, and the preemption starvation guard."""

import time

import jax
import numpy as np
import pytest

from repro.cluster import KVAdmissionPolicy, build_sim_cluster
from repro.core import ElasticScheduler, FixedScheduler
from repro.core.latency_model import A100_80G
from repro.models import ArchConfig, build_model
from repro.serving import (DATASETS, EngineCore, ModelBackend, NULL_TRACER,
                           PoissonWorkload, Request, SimBackend, Tracer,
                           WallClock, load_jsonl, replay_select,
                           validate_trace_events)
from repro.serving.telemetry import (COUNTER_FIELDS, build_spans,
                                     decision_summary, phase_attribution,
                                     ttft_breakdown)

CFG = ArchConfig(name="sim8b", family="dense", n_layers=36, d_model=4096,
                 n_heads=32, n_kv_heads=8, d_ff=12288, vocab_size=151936,
                 block_size=32)
PROF = DATASETS["sharegpt"]


def _backend(seed=0, kv_pages=1 << 16, **kw):
    return SimBackend(CFG, A100_80G,
                      tokens_per_step=PROF.tokens_per_step_bd32,
                      decode_mode="elastic", kv_pool_pages=kv_pages,
                      seed=seed, **kw)


def _scheduler(be):
    return ElasticScheduler.from_analytic(
        be.analytic, prior_tokens_per_step=PROF.tokens_per_step_bd32)


def _run_engine(tracer=None, n=12, seed=7, kv_pages=1 << 16, **bk):
    be = _backend(seed=seed, kv_pages=kv_pages, **bk)
    core = EngineCore(be, _scheduler(be), max_batch=64, tracer=tracer)
    core.submit_all(list(PoissonWorkload(PROF, rate=8.0, n_requests=n,
                                         seed=seed)))
    core.drain()
    return core


def _report_key(rep):
    return ([(m.rid, m.admit_time, m.first_token_time, m.finish_time,
              m.n_tokens, m.computed_tokens, m.decode_steps, m.preemptions)
             for m in rep.metrics],
            rep.chunk_history, rep.total_tokens, rep.computed_tokens)


def _traced_cluster(n_replicas=2, n_req=40, rate=25.0, kv_pages=2048,
                    preemption=False, seed=3):
    tr = Tracer()
    cluster = build_sim_cluster(CFG, PROF, n_replicas, "saturation",
                                kv_pages=kv_pages, preemption=preemption,
                                prefill_mode="chunked", seed=seed, tracer=tr)
    reqs = list(PoissonWorkload(PROF, rate=rate, n_requests=n_req,
                                seed=seed))
    if preemption:
        for r in reqs:
            r.priority = 1 if r.rid % 4 == 0 else 0
    rep = cluster.run(reqs)
    return tr, cluster, rep


# ---------------------------------------------------------------------------
# null tracer: no-op object, zero perturbation
# ---------------------------------------------------------------------------

def test_null_tracer_is_default_and_inert():
    core = _run_engine(tracer=None, n=6)
    assert core.tracer is NULL_TRACER
    assert NULL_TRACER.enabled is False
    # the null tracer records nothing and every method returns None
    assert NULL_TRACER.tick(core, 0.0, 0.0, 1, 8) is None
    assert NULL_TRACER.req("submit", 0, 0.0) is None
    assert NULL_TRACER.counter("x", 0.0, 1) is None


def test_tracing_does_not_perturb_the_run():
    """Telemetry observes the virtual timeline; traced and untraced twins
    must produce identical reports."""
    plain = _run_engine(tracer=None, n=15)
    traced = _run_engine(tracer=Tracer(), n=15)
    assert _report_key(plain.report()) == _report_key(traced.report())


# ---------------------------------------------------------------------------
# tick events: scheduler inputs + outputs, counters, gauges
# ---------------------------------------------------------------------------

def test_tick_events_carry_decision_and_match_history():
    tr = Tracer()
    core = _run_engine(tracer=tr, n=12)
    recs = tr.records()
    ticks = [r for r in recs if r["kind"] == "tick"]
    hist = core.report().chunk_history
    assert len(ticks) == len(hist)
    for rec, (t, b, chunk) in zip(ticks, hist):
        assert rec["chunk"] == chunk
        assert rec["b"] == b
        assert rec["t"] + rec["dur"] == pytest.approx(t)
        d = rec["decision"]
        assert d["chunk"] == chunk                 # decision chose the tick
        assert set(d) >= {"b", "kv_util", "prefill_tokens", "cap", "cur",
                          "held", "tu", "scores", "candidates"}
        # allocator gauges and backend counters sampled every tick
        assert rec["gauges"]["pages_in_use"] + rec["gauges"]["free_pages"] \
            == rec["gauges"]["n_pages"]
        assert rec["counters"]["decode_dispatches"] >= 0
        assert "host_transfer_bytes" in rec["counters"]


def test_tick_durations_on_a_virtual_clock_are_the_backend_latency():
    """On the virtual clock a tick lasts exactly (bit for bit) the latency
    the backend reported, and the engine's busy time adds them up with
    the prefill latencies of admission."""
    be = _backend(seed=7)
    step_lats, admit_lats = [], []
    step, admit = be.decode_step, be.admit

    def decode_step(rids, chunk):
        lat, infos = step(rids, chunk)
        step_lats.append(lat)
        return lat, infos

    def admit_rec(req):
        admit_lats.append(admit(req))
        return admit_lats[-1]
    be.decode_step, be.admit = decode_step, admit_rec
    tr = Tracer()
    core = EngineCore(be, _scheduler(be), max_batch=64, tracer=tr)
    core.submit_all(list(PoissonWorkload(PROF, rate=8.0, n_requests=12,
                                         seed=7)))
    core.drain()
    ticks = [r for r in tr.records() if r["kind"] == "tick"]
    assert [r["dur"] for r in ticks] == step_lats and sum(step_lats) > 0
    assert core.report().busy_time == pytest.approx(sum(step_lats)
                                                    + sum(admit_lats))
    steps = [r["tick"] for r in ticks]
    assert steps == sorted(set(steps))
    assert 1 <= steps[0] and steps[-1] <= core._steps


def test_tick_durations_on_a_wall_clock_are_the_step_time():
    """With a real model on the wall clock the backend reports no latency:
    a tick lasts the wall time its decode step took, and the ticks add up
    to the time the engine spent in its decode iterations."""
    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                     block_size=8)
    model = build_model(cfg)
    be = ModelBackend(model, model.init(jax.random.PRNGKey(0)), max_len=64)
    tr = Tracer()
    core = EngineCore(be, FixedScheduler(8), max_batch=4, clock=WallClock(),
                      tracer=tr)
    spent = []
    decode_once = core._decode_once

    def timed():
        t0 = time.perf_counter()
        decode_once()
        spent.append(time.perf_counter() - t0)
    core._decode_once = timed
    rng = np.random.default_rng(0)
    core.submit_all([Request(i, 0.0, 12, 16, prompt_tokens=rng.integers(
        4, cfg.vocab_size, 12).tolist()) for i in range(4)])
    core.drain()
    durs = [r["dur"] for r in tr.records() if r["kind"] == "tick"]
    assert len(durs) == len(spent) and all(d > 0 for d in durs)
    assert sum(durs) == pytest.approx(sum(spent), rel=0.10)
    assert core.report().busy_time == pytest.approx(sum(durs))


def test_fixed_scheduler_decisions_logged():
    be = _backend()
    core = EngineCore(be, FixedScheduler(8), tracer=Tracer())
    core.submit_all(list(PoissonWorkload(PROF, rate=5.0, n_requests=4,
                                         seed=1)))
    core.drain()
    ticks = [r for r in core.tracer.records() if r["kind"] == "tick"]
    assert ticks and all(r["decision"]["policy"] == "fixed" and
                         r["decision"]["chunk"] == 8 for r in ticks)


# ---------------------------------------------------------------------------
# ISSUE acceptance: replaying ElasticScheduler.select from the log
# reproduces the logged chunk for every tick
# ---------------------------------------------------------------------------

def test_replay_select_reproduces_every_logged_decision():
    tr, cluster, _ = _traced_cluster(n_replicas=2, n_req=40, rate=25.0)
    ticks = [r for r in tr.records() if r["kind"] == "tick"]
    assert len(ticks) > 50
    for rec in ticks:
        d = rec["decision"]
        sch = cluster.replicas[rec["replica"]].scheduler
        assert replay_select(sch, d) == d["chunk"] == rec["chunk"]


def test_replay_select_survives_json_roundtrip(tmp_path):
    """JSON stringifies the int dict keys in tu/scores; replay must still
    work from a loaded file, not just in-memory dicts."""
    tr, cluster, _ = _traced_cluster(n_replicas=1, n_req=15, rate=10.0)
    path = str(tmp_path / "trace.jsonl")
    tr.to_jsonl(path)
    ticks = [r for r in load_jsonl(path) if r["kind"] == "tick"]
    assert ticks
    for rec in ticks:
        d = rec["decision"]
        assert replay_select(cluster.replicas[0].scheduler, d) == d["chunk"]


# ---------------------------------------------------------------------------
# request lifecycle spans
# ---------------------------------------------------------------------------

def test_lifecycle_spans_ordered():
    tr, _, rep = _traced_cluster(n_replicas=2, n_req=30, rate=20.0)
    spans = build_spans(tr.records())
    assert len(spans) == len(rep.metrics)
    for s in spans.values():
        assert s["submit"] is not None and s["admits"] and \
            s["first_token"] is not None and s["finish"] is not None
        assert s["submit"] <= min(s["admits"])
        assert min(s["admits"]) <= s["first_token"] <= s["finish"]
        assert s["queue_wait"] >= 0 and s["ttft"] >= 0
        assert s["replica"] in (0, 1)
    m_by_rid = {m.rid: m for m in rep.metrics}
    for rid, s in spans.items():
        assert s["finish"] == pytest.approx(m_by_rid[rid].finish_time)
        assert s["ttft"] == pytest.approx(m_by_rid[rid].ttft)


def test_preempted_request_span_has_preempt_and_readmit():
    tr, _, rep = _traced_cluster(n_replicas=2, n_req=40, rate=40.0,
                                 kv_pages=192, preemption=True)
    assert rep.preemptions > 0
    spans = build_spans(tr.records())
    pre = [s for s in spans.values() if s["n_preempts"] > 0]
    assert pre
    for s in pre:
        # evicted then re-admitted: one more admit than evictions at most,
        # and every preempt carries a reason
        assert len(s["admits"]) >= 2
        assert all(reason in ("memory", "cluster") for _, reason
                   in s["preempts"])
    recs = tr.records()
    m_by_rid = {m.rid: m for m in rep.metrics}
    for r in recs:
        if r["kind"] == "preempt":
            assert r["pages_freed"] >= 0
            assert m_by_rid[r["rid"]].preemptions >= 1


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_perfetto_export_valid_with_counter_tracks(tmp_path):
    tr, _, _ = _traced_cluster(n_replicas=2, n_req=25, rate=20.0)
    doc = tr.to_perfetto(str(tmp_path / "t.perfetto.json"))
    assert validate_trace_events(doc) == []
    assert validate_trace_events(str(tmp_path / "t.perfetto.json")) == []
    evs = doc["traceEvents"]
    # one process per replica, named
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names == {"replica 0", "replica 1"}
    # counter registry fields surface as counter tracks
    counter_names = {e["name"] for e in evs if e["ph"] == "C"}
    for want in ("kv_util", "bc", "pages_in_use", "host_transfer_bytes",
                 "decode_dispatches"):
        assert want in counter_names and want in COUNTER_FIELDS
    # request spans open and close
    assert any(e["ph"] == "b" for e in evs)
    assert any(e["ph"] == "e" for e in evs)


def test_validate_trace_events_catches_malformed():
    assert validate_trace_events({"foo": 1})
    bad = {"traceEvents": [
        {"ph": "Z", "name": "x", "pid": 0, "ts": 0},          # bad phase
        {"ph": "X", "name": "x", "pid": 0, "ts": 0},          # missing dur
        {"ph": "C", "name": "x", "pid": 0, "ts": 0,
         "args": {"value": "high"}},                          # non-numeric
        {"ph": "b", "name": "x", "pid": 0, "ts": 0},          # no id/cat
    ]}
    errs = validate_trace_events(bad)
    assert len(errs) == 4


def test_jsonl_roundtrip_and_analysis(tmp_path):
    tr, _, _ = _traced_cluster(n_replicas=2, n_req=25, rate=20.0)
    path = str(tmp_path / "trace.jsonl")
    jsonl, perfetto = tr.export(path)
    assert jsonl == path and perfetto.endswith(".perfetto.json")
    recs = load_jsonl(path)
    assert len(recs) == len(tr.events)
    ds = decision_summary(recs)
    assert ds["n_ticks"] == sum(r["kind"] == "tick" for r in recs)
    assert sum(row["ticks"] for row in ds["per_chunk"].values()) \
        == ds["n_ticks"]
    pa = phase_attribution(recs)
    assert set(pa) == {0, 1}
    for a in pa.values():
        assert a["busy"] == pytest.approx(
            a["decode"] + a["mixed"] + a["prefill_only"])
        assert 0.0 <= a["utilization"] <= 1.0 + 1e-9
    tb = ttft_breakdown(build_spans(recs))
    assert tb["n_requests"] > 0
    assert 0.0 <= tb["queue_wait_share"] <= 1.0


def test_ring_buffer_bounds_memory():
    tr = Tracer(max_events=64)
    be = _backend(seed=2)
    core = EngineCore(be, _scheduler(be), tracer=tr)
    core.submit_all(list(PoissonWorkload(PROF, rate=8.0, n_requests=20,
                                         seed=2)))
    core.drain()
    assert len(tr.events) == 64
    assert tr.dropped > 0
    # a truncated trace is still a valid trace of its suffix
    assert validate_trace_events(tr.to_perfetto()) == []


def test_ad_hoc_counter_series():
    tr = Tracer()
    tr.counter("spill_queue", 0.5, 3, replica=1)
    doc = tr.to_perfetto()
    evs = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert evs and evs[0]["name"] == "spill_queue" \
        and evs[0]["args"]["value"] == 3 and evs[0]["pid"] == 1


# ---------------------------------------------------------------------------
# starvation guard: bounded per-request preemptions
# ---------------------------------------------------------------------------

def _tiny_core(preemption_cap=2):
    be = _backend(seed=5, kv_pages=4096)
    core = EngineCore(be, _scheduler(be), preemption_cap=preemption_cap)
    reqs = [Request(rid=i, arrival_time=0.0, prompt_len=64,
                    max_new_tokens=64) for i in range(3)]
    core.submit_all(reqs)
    now = core.clock.now()
    core._admit(now)
    assert core.n_active == 3
    return core


def test_memory_victim_skips_requests_at_cap():
    core = _tiny_core(preemption_cap=2)
    # rid 0 would normally be victim-ranked first among equals is not
    # guaranteed; instead pin the count: saturate rid of the default victim
    v0 = core._memory_victim()
    core._metrics[v0.rid].preemptions = 2          # at cap
    v1 = core._memory_victim()
    assert v1.rid != v0.rid
    assert core.preemption_count(v0.rid) >= core.preemption_cap


def test_memory_victim_waives_cap_when_all_saturated():
    core = _tiny_core(preemption_cap=1)
    for r in core.active_requests():
        core._metrics[r.rid].preemptions = 5       # everyone past the cap
    # memory safety first: a victim is still produced
    assert core._memory_victim() is not None


def test_cluster_preemption_victims_respect_cap():
    be = _backend(seed=6, kv_pages=32)
    core = EngineCore(be, _scheduler(be), preemption_cap=2)
    low = [Request(rid=i, arrival_time=0.0, prompt_len=128,
                   max_new_tokens=128, priority=0) for i in range(3)]
    core.submit_all(low)
    core._admit(core.clock.now())
    assert core.n_active >= 2
    policy = KVAdmissionPolicy(low_watermark=0.0)
    high = Request(rid=99, arrival_time=1.0, prompt_len=256,
                   max_new_tokens=128, priority=1)
    victims = policy.preemption_victims(core, high)
    assert victims                                  # eviction can help
    # saturate every active request's eviction count: the cluster tier must
    # now refuse to preempt (spill instead) — no waiver at this tier
    for r in core.active_requests():
        core._metrics[r.rid].preemptions = 2
    assert policy.preemption_victims(core, high) == []

"""One run of one cell: set-up, open-loop traffic on the wall clock, the
measured window, the drain, the check against the reference, the result.

The served path is the program's own: ``launch.serve.model_serving``
builds the ``ModelBackend`` (its page pool as the configuration sets it,
Pallas kernel attention) and the elastic scheduler, and
``EngineCore.tick`` drives them.  The harness only wraps bound methods of
those instances to record what ran, and, with ``--trace 1``, to open
profiler spans around the calls into each layer.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

import numpy as np

from bench import flops as F
from bench import spec as S
from bench import stats
from bench import traffic as T
from bench.clock import CompileCounter, WallClock

CACHE_DIR = S.ROOT / ".jax_cache"
OUT_DIR = S.ROOT / "bench_out"
# the check's sample: the longest finished request of the window, then
# others drawn from the seed among those decoded in a batch with it,
# preferring requests of at least SAMPLE_MIN_TOKENS served tokens
SAMPLE_SLOTS = 4
SAMPLE_MIN_TOKENS = 150
TRACE_SECONDS = 4.0
# the gap reported when nothing could be compared
NO_GAP = 1e30
# a CPU rehearsal (tests only) runs the cell's mix at these sizes
REHEARSAL = {"prompt_max": 40, "output_max": 20, "rate": 1.5,
             "lead_in_s": 1.0, "drain_s": 60.0, "max_batch": 4}
# the keys of a configuration's "serving" object: ModelBackend options
SERVING_OPTIONS = ("prefill_token_budget",)
# programs the traced run names spans after: (owner, method, span)
SPANS = (("core", "_admit", "bench.engine.admit"),
         ("core", "_decode_once", "bench.engine.tick"),
         ("sched", "select", "bench.scheduler.select"),
         ("backend", "decode_step", "bench.backend.decode_step"),
         ("backend", "_prefill_tick", "bench.backend.prefill_tick"),
         ("backend", "_dispatch_window", "bench.backend.decode_dispatch"))


def configure_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program kept.  Call before JAX compiles anything."""
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)


def weight_seed(seed: int) -> int:
    """A 32-bit key seed from any run seed (JAX keeps 32 bits)."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


@dataclass
class Tick:
    t0: float
    t1: float = 0.0
    plan: list = field(default_factory=list)     # [(rid, offset, n)]
    dec: tuple | None = None   # (rids, win, start, valid, n_adv)
    commits: dict = field(default_factory=dict)  # rid -> commit mask
    n_committed: int = 0


class Recorder:
    """Tracer hook of the engine plus wrappers on the backend: per tick what
    ran (prefill plan, decode window) and what it committed; the admits
    and finishes; every finished request's served tokens."""

    def __init__(self, backend, clock, spec, peak=None):
        self.clock, self.spec, self.peak = clock, spec, peak
        self.ticks: list[Tick] = []
        self.admits: dict[int, int] = {}      # rid -> tick index at admit
        self.finished: dict[int, float] = {}
        self.served: dict[int, np.ndarray] = {}
        self._cur = None
        self.marks = False
        decode_step, dispatch, release = (backend.decode_step,
                                          backend._dispatch_window,
                                          backend.release)

        def decode_step_rec(rids, chunk):
            self._cur = rec = Tick(self.clock.now())
            lat, infos = decode_step(rids, chunk)
            rec.t1 = self.clock.now()
            rec.plan = backend.last_prefill_plan
            if rec.dec is not None:
                rec.commits = {r: infos[r].commit_mask for r in rec.dec[0]}
            rec.n_committed = sum(i.n_committed for i in infos.values())
            self.ticks.append(rec)
            if self.marks and self.peak is not None:
                self._mark(rec)
            return lat, infos

        def dispatch_rec(rids, win, start, valid, n_adv):
            out = dispatch(rids, win, start, valid, n_adv)
            self._cur.dec = (list(rids), win, start, valid, n_adv)
            return out

        def release_rec(rid):
            st = backend.state(rid)
            if st.done:
                self.served[rid] = np.asarray(st.output_tokens, np.int64)
            release(rid)

        backend.decode_step = decode_step_rec
        backend._dispatch_window = dispatch_rec
        backend.release = release_rec

    # -- the engine's tracer protocol -----------------------------------
    def tick(self, core, t0, dur, b, chunk, commits=0):
        pass

    def req(self, kind, rid, t, replica=0, **payload):
        if kind == "admit":
            self.admits[rid] = len(self.ticks)
        elif kind == "finish":
            self.finished[rid] = t

    # -- traced runs ----------------------------------------------------
    def _mark(self, rec: Tick):
        import jax
        ops, nbytes, bound = attn_work(self.spec, rec, self.peak)
        with jax.profiler.TraceAnnotation(
                "bench.mark.tick", prefill_tokens=sum(n for _, _, n
                                                       in rec.plan),
                attn_ops=ops, attn_bytes=nbytes, attn_bound_s=bound):
            pass


def attn_work(spec: dict, rec: Tick, peak: dict):
    """(ops, bytes, roofline seconds) of the paged-attention kernel calls
    of one tick: its prefill dispatch and its decode dispatch, every
    layer.  Each call's bound is max(ops / peak FLOP/s, bytes / peak
    bytes/s)."""
    L = spec["num_hidden_layers"]
    calls = []
    if rec.plan:
        calls.append(([o for _, o, _ in rec.plan], [n for _, _, n in rec.plan]))
    if rec.dec is not None:
        calls.append((rec.dec[2], rec.dec[3]))
    ops = nbytes = bound = 0.0
    for ctx, rows in calls:
        o, b = F.paged_attn_call(spec, ctx, rows)
        ops, nbytes = ops + L * o, nbytes + L * b
        bound += L * max(o / peak["flops"], b / peak["bytes_per_s"])
    return ops, nbytes, bound


def tick_flops(model, spec: dict, rec: Tick) -> float:
    """Model FLOPs of one tick's dispatches, by ``model``'s (a model
    module's) count."""
    total = model.prefill_flops(spec, [(o, n) for _, o, n in rec.plan])
    if rec.dec is not None:
        total += model.decode_flops(spec, rec.dec[2], rec.dec[3])
    return total


def serving_kw(config: dict) -> dict:
    """The program's serving options a configuration sets: the page pool
    in tokens (``kv_pool_tokens``; default: the HBM left after the
    weights), and its ``serving`` object, whose keys are
    ``SERVING_OPTIONS``, passed to ``model_serving`` as they are."""
    kw = {}
    if config.get("kv_pool_tokens"):
        kw["kv_pages"] = config["kv_pool_tokens"] // config["kv_page_size"]
    opts = config.get("serving", {})
    unknown = sorted(set(opts) - set(SERVING_OPTIONS))
    if unknown:
        raise ValueError(f"unknown serving option(s) {unknown} in "
                         f"configuration {config.get('name')!r}; allowed: "
                         + ", ".join(SERVING_OPTIONS))
    kw.update(opts)
    return kw


def serve(config: dict, model, spec: dict, wseed: int, requests,
          mode: str, dataset: str, seed: int):
    """(program model, backend, scheduler): the configuration's model by
    its model module ``model``, with weights from ``wseed`` and the spec's
    logit gain as the final norm's scale, served by the program's
    ``model_serving`` with the configuration's serving options and the
    ``mode`` scheduler."""
    import jax

    from repro.launch.serve import init_model, model_serving
    from repro.models.common import ArchConfig
    from repro.serving import DATASETS
    lm, params = init_model(ArchConfig(**model.arch_kwargs(spec)), wseed)
    params["final_norm"]["scale"] = jax.numpy.full_like(
        params["final_norm"]["scale"], spec["logit_gain"])
    backend, sched = model_serving(lm, params, requests, mode,
                                   DATASETS[dataset], seed=seed,
                                   attn_impl="kernel", **serving_kw(config))
    return lm, backend, sched


def rehearsal_traffic(traffic: dict) -> dict:
    """A cell's mix at the sizes a CPU rehearsal runs (tests only)."""
    return dict(traffic,
                prompt=dict(traffic["prompt"], max=REHEARSAL["prompt_max"]),
                output=dict(traffic["output"], max=REHEARSAL["output_max"]),
                arrivals=dict(traffic["arrivals"], rate=REHEARSAL["rate"]),
                lead_in_s=REHEARSAL["lead_in_s"],
                drain_s=REHEARSAL["drain_s"])


def pow2s(limit: int):
    p = 1
    while p <= limit:
        yield p
        p *= 2


def pow2_ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def prefill_shapes(budget: int, align: int, max_batch: int,
                   min_prompt: int, max_prompt: int) -> list:
    """Every (rows, tokens) bucket a prefill dispatch can take.

    A tick's plan (``PrefillScheduler.plan``) hands out at most ``budget``
    prompt tokens: its first entry may continue a prompt (≥ 1 token), every
    later one starts a prompt and takes either all of it (≥ ``min_prompt``)
    or an aligned chunk (≥ ``align``).  Rows pad to the next power of two
    of the entries, tokens to that of the largest entry."""
    m = min(min_prompt, align)
    n_cap = min(budget, max_prompt)
    out = []
    for Bp in pow2s(max_batch):
        k = 1 if Bp == 1 else Bp // 2 + 1
        for Tp in pow2s(pow2_ceil(n_cap)):
            n = 1 if Tp == 1 else Tp // 2 + 1
            if k >= 2:
                n = max(n, m)
                if n > Tp:
                    continue
            least = n + (1 if k >= 2 else 0) + max(k - 2, 0) * m
            if n <= n_cap and least <= budget:
                out.append((Bp, Tp))
    return out


def prefill_budget(backend) -> int:
    """The most prompt tokens a tick's prefill plan can hand out: the
    configured budget, or the adaptive one's target."""
    pf = backend._prefill
    return max(pf.target_bc or pf.budget, pf.align)


def warm_runs(backend, max_batch: int, candidates, traffic: dict) -> list:
    """Every dispatch shape the mix can reach: ("decode", rows, chunk) and
    ("prefill", rows, tokens), by the backend's prefill budget."""
    runs = [("decode", B, c) for B in pow2s(max_batch) for c in candidates]
    runs += [("prefill", B, n) for B, n in prefill_shapes(
        prefill_budget(backend), backend._prefill.align, max_batch,
        traffic["prompt"]["min"], traffic["prompt"]["max"])]
    return runs


def warm(backend, spec: dict, max_batch: int, candidates, traffic: dict,
         log=print):
    """Run every decode and prefill shape the mix can reach once, with
    rows that write nothing (valid 0), so nothing compiles later.  A shape
    the compiler refuses for memory is logged and left out."""
    import jax
    import jax.numpy as jnp
    i32 = jnp.int32
    W = backend._table_width
    mask = spec["mask_token_id"]
    runs = warm_runs(backend, max_batch, candidates, traffic)
    refused = []
    for kind, B, n in runs:
        z = jnp.asarray(np.zeros(B, np.int64), i32)
        tables = jnp.asarray(np.zeros((B, W), np.int32))
        try:
            if kind == "decode":
                w = jnp.asarray(np.full((B, n), mask, np.int64), i32)
                conf, _, pages = backend._decode_paged(
                    backend.params, backend._pages_cache(), w, z, z, tables,
                    z, z)
            else:
                toks = jnp.asarray(np.zeros((B, n), np.int32))
                conf, _, pages = backend._prefill_chunk(
                    backend.params, backend._pages_cache(), toks, z, z,
                    tables)
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            refused.append((kind, B, n))
            continue
        backend._store_pages(pages)
        np.asarray(conf)
    log(f"warm-up: {len(runs) - len(refused)} shapes of {len(runs)} "
        f"(decode rows x chunk, prefill rows x tokens; prefill budget "
        f"{prefill_budget(backend)} tokens); refused by the compiler for memory: "
        f"{refused or 'none'}")
    return refused


def prefill_shapes_used(ticks) -> set:
    return {(pow2_ceil(len(t.plan)), pow2_ceil(max(n for *_, n in t.plan)))
            for t in ticks if t.plan}


def install_spans(objs: dict):
    """Wrap the layer entry points in profiler spans (traced runs)."""
    import functools

    import jax
    for owner, meth, span in SPANS:
        obj = objs[owner]
        fn = getattr(obj, meth)

        def wrapped(*a, _fn=fn, _span=span, **k):
            with jax.profiler.TraceAnnotation(_span):
                return _fn(*a, **k)
        setattr(obj, meth, functools.wraps(fn)(wrapped))


@dataclass
class RunData:
    """What a per-layer metric's reader gets."""
    spec: dict
    peak: dict | None
    sampled: list           # RequestMetrics of requests due in the window
    ticks: list             # [Tick] whose step ended in the window
    trace: object | None    # bench.trace.TraceSummary
    model: object = None    # the configuration's model module
    # bench.spans.SpanSummary: the program's serving.* spans and their
    # stats (its counters) in the traced window; None on an untraced run
    spans: object | None = None


def build_sample(rec: Recorder, arrivals: dict, sampled_rids, seed):
    """The requests the check replays: the longest finished request due in
    the window, then others drawn from the seed among the finished ones
    that shared a decode batch with it, those with at least
    ``SAMPLE_MIN_TOKENS`` served tokens first, so that the compared tokens
    come from several rows of the batch."""
    from bench.reference import RequestTrace, Window
    done = sorted(r for r in sampled_rids if r in rec.served)
    if not done:
        return []
    rng = np.random.default_rng(seed)
    longest = max(done, key=lambda r: (len(rec.served[r]), -r))
    batched = set()
    for tk in rec.ticks:
        if tk.dec is not None and longest in tk.dec[0]:
            batched.update(tk.dec[0])
    rest = [r for r in rng.permutation(done).tolist() if r != longest]
    rest.sort(key=lambda r: (r not in batched,
                             len(rec.served[r]) < SAMPLE_MIN_TOKENS))
    out = []
    for rid in [longest] + rest[:SAMPLE_SLOTS - 1]:
        a = arrivals[rid]
        served = rec.served[rid]
        tr = RequestTrace(rid, a.tokens, served)
        seen = np.zeros(len(served), bool)   # committed in an earlier window
        for tk in rec.ticks[rec.admits[rid]:]:
            if tk.dec is None or rid not in tk.commits:
                continue
            i = tk.dec[0].index(rid)
            start, valid = int(tk.dec[2][i]), int(tk.dec[3][i])
            committed = np.asarray(tk.commits[rid], bool)
            c = len(committed)
            rel = start - len(a.tokens) + np.arange(c)
            inside = (np.arange(c) < valid) & (rel < len(served))
            relc = np.clip(rel, 0, len(served) - 1)
            open_ = inside & ~seen[relc]
            tr.windows.append(Window(
                start, valid, np.asarray(tk.dec[1][i], np.int32),
                committed, open_,
                np.where(committed & inside, served[relc], 0)
                .astype(np.int32)))
            seen[relc[committed & inside]] = True
        out.append(tr)
    return out


def uncompared(sample) -> int:
    """Served tokens of the sample that no recorded window committed."""
    missing = 0
    for r in sample:
        seen = np.zeros(len(r.served), bool)
        for wd in r.windows:
            rel = wd.start - len(r.prompt) + np.nonzero(wd.committed)[0]
            seen[rel[(rel >= 0) & (rel < len(seen))]] = True
        missing += int((~seen).sum())
    return missing


def check(model, spec, wseed, sample, max_ctx, limits, control=False):
    """Replay the sample on the reference of ``model`` (a model module);
    the numbers ``correct`` compares, each with its limit, and what was
    counted on the way.

    With ``control`` the reference computed in fp8 takes the program's
    place: its choice of positions and tokens in every window of the
    sample is judged as the program's is, and those are the numbers
    returned; the program's own stand in ``info["program"]``."""
    from bench import reference as R
    thr = spec["confidence_threshold"]
    decisions = alt = None
    if control and sample:
        ctl = R.replay(model, spec, wseed, sample, max_ctx, quant="fp8")
        decisions = R.control_decisions(sample, ctl, thr)
        alt = {k: out for k, (_, out) in decisions.items()}
    ref = R.replay(model, spec, wseed, sample, max_ctx, alt=alt) \
        if sample else {}
    missing = uncompared(sample)

    def numbers(j):
        return {
            "logit_gap": {"value": NO_GAP if j["logit_gap"] is None
                          else j["logit_gap"], "limit": limits["logit_gap"]},
            "commit_gap": {"value": j["commit_gap"],
                           "limit": limits["commit_gap"]},
            "uncompared_tokens": {"value": missing, "limit": 0},
        }
    prog = R.judge(sample, ref, thr)
    info = dict(prog, requests=len(sample),
                served=[len(r.served) for r in sample])
    if not control:
        return numbers(prog), info
    judged = R.judge(sample, ref, thr, decisions) if sample else prog
    info["program"] = numbers(prog)
    info["control"] = {k: judged[k] for k in ("compared", "argmax_agree")}
    return numbers(judged), info


def free(tree):
    import jax
    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array) and not x.is_deleted():
            x.delete()


def run_cell(cell: S.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, rehearse: bool = False, on_backend=None,
             control: bool = False, log=print) -> dict:
    """Run ``cell`` once; returns the result line's object.  With
    ``control`` the check judges the fp8 control in the program's place
    (see :func:`check`)."""
    import jax

    from repro.serving.engine import EngineCore
    from repro.serving.request import Request

    devs = jax.devices()
    dev = devs[0]
    compiles = CompileCounter().install()
    spec = S.model_spec(cell.config, smoke=rehearse, model=cell.model)
    traffic = dict(cell.traffic)
    max_batch = cell.config["max_batch"]
    if rehearse:
        traffic = rehearsal_traffic(traffic)
        max_batch = REHEARSAL["max_batch"]
    peak = None if rehearse else F.peak(dev.device_kind)
    wseed = weight_seed(seed)

    # -- set-up ---------------------------------------------------------
    arrivals = T.generate(traffic, seed, seconds, spec["vocab_size"])
    requests = [Request(a.rid, a.due, a.prompt_len, a.output_len,
                        prompt_tokens=a.tokens.tolist(),
                        dataset=traffic["dataset"]) for a in arrivals]
    max_ctx = T.longest_context(traffic)
    span = Request(-1, 0.0, traffic["prompt"]["max"],
                   traffic["output"]["max"], prompt_tokens=[])
    lm, backend, sched = serve(cell.config, cell.model, spec, wseed,
                               requests + [span], "elastic",
                               traffic["dataset"], seed)
    if not rehearse:
        warm(backend, spec, max_batch, sched.candidates, traffic, log=log)
    if on_backend is not None:
        on_backend(backend)
    clock = WallClock()
    rec = Recorder(backend, clock, spec, peak)
    core = EngineCore(backend, sched, max_batch=max_batch, clock=clock,
                      tracer=rec)
    if trace:
        install_spans({"core": core, "sched": sched, "backend": backend})
    kv = backend.kv
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.2f} s: {compiles.compiled} programs compiled, "
        f"{compiles.loaded} loaded from the cache ({CACHE_DIR}); pool "
        f"{kv.n_pages} pages = {kv.n_pages * kv.page_size} tokens; "
        f"max_batch {max_batch}; {len(requests)} requests due over "
        f"{traffic['lead_in_s'] + seconds + traffic['drain_s']:.0f} s")

    # -- traffic: lead-in, window, drain ----------------------------------
    lo = traffic["lead_in_s"]
    hi = lo + seconds
    limit = hi + traffic["drain_s"]
    sampled = [r.rid for r in requests if stats.in_window(r.arrival_time,
                                                          lo, hi)]
    todo = set(sampled)
    # the traced seconds sit in the middle of the window
    t_len = min(TRACE_SECONDS, 0.3 * seconds)
    t_lo = lo + (seconds - t_len) / 2
    tdir, tspan, traced = OUT_DIR / f"trace-{os.getpid()}", None, False
    c_lo = c_hi = None
    core.submit_all(requests)
    while True:
        now = clock.now()
        if c_lo is None and now >= lo:
            c_lo = compiles.programs
        if c_hi is None and now >= hi:
            c_hi = compiles.programs
        if trace and not traced and now >= t_lo:
            shutil.rmtree(tdir, ignore_errors=True)
            jax.profiler.start_trace(str(tdir))
            tspan = jax.profiler.TraceAnnotation("bench.window")
            tspan.__enter__()
            rec.marks = traced = True
        if tspan is not None and now >= t_lo + t_len:
            rec.marks = False
            tspan.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tspan = None
        todo -= rec.finished.keys()
        if now >= hi and (not todo or now >= limit):
            break
        if not core.tick():
            if now >= hi:
                break
            clock.advance_to(hi)
    end = clock.now()
    c_hi = compiles.programs if c_hi is None else c_hi
    window_compiles = c_hi - c_lo
    peak_bytes = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    # -- end-to-end metrics ----------------------------------------------
    mets = {m.rid: m for m in core.report().metrics}
    for r in core.active_requests() + core.pending_requests():
        m = core._metrics.get(r.rid)
        if m is not None:
            mets.setdefault(r.rid, m)
    sm = [mets.get(rid) for rid in sampled]
    failed = sum(1 for rid in sampled if rid not in rec.finished)
    arrival = {r.rid: r.arrival_time for r in requests}

    def ttft(rid, m):
        return (m.first_token_time if m is not None
                and m.first_token_time >= 0 else end) - arrival[rid]

    def tpot(rid, m):
        if rid in rec.finished and m.n_tokens > 1:
            return m.tpot
        return end - arrival[rid]      # missed: at least the whole run

    def decode_span(rid, m):
        """(seconds from first to last token, tokens after the first); a
        request unfinished at the drain limit counts until then."""
        if m is None or m.first_token_time < 0:
            return end - arrival[rid], 0
        stop = m.finish_time if rid in rec.finished else end
        return stop - m.first_token_time, max(m.n_tokens - 1, 0)

    win_ticks = [t for t in rec.ticks if stats.in_window(t.t1, lo, hi)]
    ttfts = [ttft(r, m) for r, m in zip(sampled, sm)]
    tpots = [tpot(r, m) for r, m in zip(sampled, sm)]
    e2e = {f"{name}_p{q}_ms": 1e3 * stats.percentile(v, q)
           for name, v in (("ttft", ttfts), ("tpot", tpots))
           for q in (50, 90)}
    e2e["tpot_mean_ms"] = 1e3 * stats.per_token(
        [decode_span(r, m) for r, m in zip(sampled, sm)])
    e2e.update(tokens_per_s=stats.rate(
        sum(t.n_committed for t in win_ticks), seconds), setup_s=setup_s)
    dec = [t for t in win_ticks if t.dec is not None]
    log(f"window [{lo:.1f}, {hi:.1f}) s: {len(sampled)} requests due, "
        f"{len(sampled) - failed} finished by {end:.1f} s (drain limit "
        f"{limit:.0f} s); {len(win_ticks)} ticks, {len(dec)} decode "
        f"dispatches, {sum(t.n_committed for t in win_ticks)} tokens "
        f"committed; chunk sizes used "
        f"{sorted({int(t.dec[1].shape[1]) for t in dec})}; prefill "
        f"shapes used {sorted(prefill_shapes_used(win_ticks))}; "
        f"{core.preemptions} preemptions")
    log(f"compiles inside the window: {window_compiles}")
    log("end-to-end: " + ", ".join(f"{k} {v:.4f}" for k, v in e2e.items()))

    # -- per-layer metrics (traced run) ------------------------------------
    summary = spans = None
    if traced:
        from bench.spans import read_spans
        from bench.trace import load_profile, reduce_trace
        profile = load_profile(next(tdir.rglob("*.xplane.pb")))
        if not rehearse:
            summary = reduce_trace(profile)
            log(f"trace: {summary.window_s:.3f} s window, device busy "
                f"{summary.busy_s:.3f} s, {len(summary.ops)} ops, "
                f"{len(summary.modules)} program runs: "
                + ", ".join(sorted({m.name for m in summary.modules})))
        spans = read_spans(profile)
        log(f"spans: {len(spans.spans)} serving spans in the traced "
            f"window, {len(spans.by_name())} names")
        shutil.rmtree(tdir, ignore_errors=True)
    data = RunData(spec, peak, sm, win_ticks, summary, cell.model, spans)
    layer = {}
    if trace:
        for m in cell.per_layer:
            v = S.metric_reader(m["name"])(data)
            if v is not None:
                layer[m["name"]] = v
        log("per-layer: " + ", ".join(f"{k} {v:.4f}"
                                      for k, v in layer.items()))

    # -- the check ---------------------------------------------------------
    sample = build_sample(rec, {a.rid: a for a in arrivals}, sampled,
                          seed + 1)
    free(backend.params)
    free(backend._pages_cache())
    del backend, core, sched, lm, rec
    gc.collect()
    t_ref = time.perf_counter()
    limits = (cell.config["smoke"] if rehearse else cell.config)["limits"]
    checks, info = check(cell.model, spec, wseed, sample, max_ctx, limits,
                         control=control)
    log(f"check: {info['requests']} requests ({info['served']} served "
        f"tokens), {info['windows']} windows, {info['compared']} served "
        f"tokens compared, {info['argmax_agree']} equal to the reference's "
        f"argmax; reference {time.perf_counter() - t_ref:.1f} s")
    if control:
        log(f"control in the program's place: {info['control']}; the "
            f"program's numbers: {info['program']}")
    ok = all(c["value"] <= c["limit"] for c in checks.values())

    names = {m["name"]: m["unit"] for m in
             (cell.per_layer if trace else cell.end_to_end)}
    values = layer if trace else e2e
    result = {
        "correct": bool(ok and not rehearse),
        "attempted": len(sampled),
        "failed": failed,
        "metrics": {} if rehearse else {
            k: {"value": values[k], "unit": u} for k, u in names.items()
            if k in values},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs), "memory_peak_bytes": peak_bytes},
    }
    if rehearse:
        result["device"] = {"platform": dev.platform, "rehearsal": True}
        result["checks_pass"] = bool(ok)
    if summary is not None:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.top_ops(),
                               "idle_gaps": summary.top_idle()}
    result["window_compiles"] = window_compiles
    if control:
        result["program_checks"] = info["program"]
    result["checks"] = checks
    return result


def main(argv=None, t_start: float | None = None):
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once (see BENCHMARK.json).")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the configuration's smoke size "
                         "(tests only): prints correct: false and no "
                         "device metric")
    args = ap.parse_args(argv)
    cell = S.resolve_cell(args.workload)
    if not args.rehearse:
        configure_cache()
    import jax
    devs = jax.devices()
    if not args.rehearse:
        if devs[0].platform != "tpu":
            sys.exit(f"bench: needs a TPU; JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
        if len(devs) < cell.chips:
            sys.exit(f"bench: {cell.name} needs {cell.chips} chips; "
                     f"{len(devs)} visible")
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=t_start, rehearse=args.rehearse,
                   log=lambda s: print(s, flush=True))
    for k, c in res["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(res), flush=True)

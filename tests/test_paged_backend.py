"""Unified paged KV layer: model-level paged/dense-cache equivalence,
kernel/ref parity, recorded-golden AR decode, prompt-pages-only admission
(Sim/Model parity), slot-recycle hygiene, and cluster-admission signal
parity.  The backend's dense-slot decode path for attention families was
retired — goldens come from the model-level dense cache (still used for
training/prefill) and teacher-forced replay, not from a dense backend."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import KVAdmissionPolicy, build_model_cluster, fits_ever
from repro.core import FixedScheduler
from repro.models import ArchConfig, build_model
from repro.serving import (DATASETS, EngineCore, ModelBackend,
                           PoissonWorkload, ServingEngine, SimBackend)
from repro.serving.kv_pool import PagedKVAllocator

CFG = ArchConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                 n_kv_heads=2, d_ff=128, vocab_size=256, block_size=8,
                 confidence_threshold=0.6)
PROF = DATASETS["sharegpt"]


@pytest.fixture(scope="module")
def model_and_params():
    model = build_model(CFG)
    return model, model.init(jax.random.PRNGKey(0))


def _requests(n, seed=0, prompt=12, out=16, simultaneous=False):
    rng = np.random.default_rng(seed)
    reqs = list(PoissonWorkload(PROF, 50.0, n, seed=seed))
    for r in reqs:
        r.prompt_len = prompt
        r.max_new_tokens = out
        r.prompt_tokens = rng.integers(4, CFG.vocab_size, prompt).tolist()
        if simultaneous:
            r.arrival_time = 0.0
    return reqs


def _run_engine(be, reqs, chunk=8, max_batch=8):
    """Run and capture each request's committed output tokens at release."""
    eng = ServingEngine(be, FixedScheduler(chunk), max_batch=max_batch)
    outs = {}
    orig_release = be.release

    def spy_release(rid):
        outs[rid] = be.state(rid).output_tokens
        orig_release(rid)

    be.release = spy_release
    rep = eng.run(reqs)
    return rep, outs


# ---------------------------------------------------------------------------
# model-level equivalence: paged prefill/chunk/freeze vs the dense cache
# (the dense cache is still the training/prefill path — it is the oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["kernel", "ref"])
def test_paged_model_path_matches_dense(model_and_params, impl):
    model, params = model_and_params
    rng = np.random.default_rng(1)
    B, max_len, ps, c = 2, 64, 8, 8
    prompts = [12, 9]
    toks = np.zeros((B, 16), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :p] = rng.integers(4, CFG.vocab_size, p)
    lens = jnp.asarray(prompts, jnp.int32)

    cache = model.init_cache(B, max_len, dtype=jnp.float32)
    logits_d, cache = model.prefill(params, jnp.asarray(toks), lens, cache)

    alloc = PagedKVAllocator(32, ps)
    for i, p in enumerate(prompts):
        alloc.allocate(i, p + 16)
    tables = jnp.asarray(alloc.batch_tables([0, 1], alloc.pages_for(max_len)))
    pcache = model.init_paged_cache(32, ps, dtype=jnp.float32)
    last_p, pcache = model.prefill_paged(params, pcache, jnp.asarray(toks),
                                         lens, tables)
    for i, p in enumerate(prompts):
        np.testing.assert_allclose(np.asarray(last_p[i]),
                                   np.asarray(logits_d[i, p - 1]),
                                   rtol=2e-5, atol=2e-5)

    win = jnp.full((B, c), CFG.mask_token_id, jnp.int32)
    start = jnp.asarray(prompts, jnp.int32)
    valid = jnp.full((B,), c, jnp.int32)
    lg_d, kv_d = model.chunk_forward(params, cache, win, start, valid)
    lg_p, kv_p = model.chunk_forward_paged(params, pcache, win, start, valid,
                                           tables, start, impl=impl)
    np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_d),
                               rtol=3e-5, atol=3e-5)

    # freeze a few window entries, then the next window must still agree
    n_adv = jnp.asarray([3, 2], jnp.int32)
    cache2 = model.freeze(cache, kv_d, start, n_adv)
    pcache2 = model.freeze_paged(pcache, kv_p, tables, start, n_adv)
    start2 = start + n_adv
    lg_d2, _ = model.chunk_forward(params, cache2, win, start2, valid)
    lg_p2, _ = model.chunk_forward_paged(params, pcache2, win, start2, valid,
                                         tables, start2, impl=impl)
    np.testing.assert_allclose(np.asarray(lg_p2), np.asarray(lg_d2),
                               rtol=3e-5, atol=3e-5)


def test_paged_rejects_recurrent_families():
    cfg = ArchConfig(name="r", family="ssm", n_layers=2, d_model=64,
                     rwkv_head_dim=16, d_ff=128, vocab_size=256,
                     diffusion=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        ModelBackend(model, params, paged=True)


def test_dense_slot_path_retired_for_attention(model_and_params):
    """Attention-only families always serve paged; the dense-slot decode
    path is gone and asking for it fails loudly, not silently."""
    model, params = model_and_params
    with pytest.raises(ValueError, match="retired"):
        ModelBackend(model, params, paged=False)
    be = ModelBackend(model, params)               # default: paged
    assert be.paged and be.kv is not None


# ---------------------------------------------------------------------------
# engine-level goldens: kernel/ref parity + teacher-forced AR replay
# ---------------------------------------------------------------------------

def test_engine_kernel_matches_ref_elastic(model_and_params):
    """The two paged attention impls must commit identical tokens through a
    ≥8-request elastic engine workload (kernel is pinned by the ref oracle
    now that the dense backend is gone)."""
    model, params = model_and_params

    def run(impl):
        be = ModelBackend(model, params, n_slots=8, max_len=64,
                          decode_mode="elastic", attn_impl=impl)
        return _run_engine(be, _requests(9))

    rep_k, out_k = run("kernel")
    rep_r, out_r = run("ref")
    assert len(rep_k.metrics) == len(rep_r.metrics) == 9
    assert out_k == out_r                     # identical committed tokens
    assert rep_k.token_utilization == rep_r.token_utilization
    assert rep_k.total_tokens == rep_r.total_tokens


def test_engine_paged_ar_matches_teacher_forcing():
    """Paged AR engine decode must equal greedy teacher-forced argmax over
    full causal forwards — the recorded-golden oracle for the paged path.
    (Needs a diffusion=False config: diffusion models prefill with a
    block-causal mask, which a causal replay would not reproduce.)"""
    cfg = ArchConfig(name="tar", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                     block_size=8, diffusion=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _requests(1, seed=4, prompt=10, out=8)
    be = ModelBackend(model, params, max_len=64, decode_mode="ar")
    _, outs = _run_engine(be, reqs, chunk=1, max_batch=2)

    toks = list(_requests(1, seed=4, prompt=10, out=8)[0].prompt_tokens)
    for _ in range(8):
        logits = model.apply(params, jnp.asarray([toks]), mask_mode="causal")
        toks.append(int(jnp.argmax(logits[0, -1])))
    assert outs[reqs[0].rid] == toks[10:]


def test_ar_single_token_request_completes(model_and_params):
    """max_new_tokens=1 AR: the prefill-derived token finishes the request
    before any decode step — the backend must not commit past gen_limit
    (regression: IndexError on ARState.committed)."""
    model, params = model_and_params
    be = ModelBackend(model, params, n_slots=2, max_len=64, decode_mode="ar")
    rep, outs = _run_engine(be, _requests(3, out=1, simultaneous=True),
                            chunk=1, max_batch=2)
    assert len(rep.metrics) == 3
    assert all(m.n_tokens == 1 for m in rep.metrics)
    assert all(len(v) == 1 for v in outs.values())


def test_engine_ar_batched_matches_solo(model_and_params):
    """Batched paged AR decode must commit the same tokens as serving each
    request alone (no cross-request contamination through the page pool)."""
    model, params = model_and_params
    reqs = _requests(5, out=8)
    be = ModelBackend(model, params, max_len=64, decode_mode="ar")
    _, out_batched = _run_engine(be, reqs, chunk=1, max_batch=4)
    for r in _requests(5, out=8):
        be1 = ModelBackend(model, params, max_len=64, decode_mode="ar")
        _, out_solo = _run_engine(be1, [r], chunk=1, max_batch=1)
        assert out_batched[r.rid] == out_solo[r.rid]


# ---------------------------------------------------------------------------
# prompt-pages-only admission (memory-elastic; Sim/Model parity)
# ---------------------------------------------------------------------------

def test_admission_is_page_bounded_not_slot_bounded(model_and_params):
    model, params = model_and_params
    # 16 simultaneous requests: the retired dense default (n_slots=8) capped
    # the batch at 8; the paged pool runs all 16 at once.
    be = ModelBackend(model, params, n_slots=8, max_len=64,
                      kv_pages=16 * 2)                 # 16 × 28tok ÷ 16/page
    rep, _ = _run_engine(be, _requests(16, simultaneous=True), max_batch=32)
    assert len(rep.metrics) == 16
    assert all(m.n_tokens == 16 for m in rep.metrics)
    assert max(rep.batch_history) > 8
    assert be.kv.free_pages == be.kv.n_pages           # pool fully drained


def test_paged_can_admit_claims_prompt_pages_only(model_and_params):
    """Admission claims ⌈prompt/page⌉ pages (growth is incremental), while
    still refusing any request whose full footprint could never fit."""
    model, params = model_and_params
    be = ModelBackend(model, params, max_len=64, kv_pages=4, page_size=16)
    reqs = _requests(4, prompt=16, out=16)       # 1 prompt page, 2 total
    for r in reqs:                               # all four 1-page prompts fit
        assert be.can_admit(r)
        assert be.admit_pages(r) == 1
        be.admit(r)
    assert be.kv.free_pages == 0
    extra = _requests(1, seed=9, prompt=16, out=16)[0]
    extra.rid = 99
    assert not be.can_admit(extra)               # no prompt page free
    be.release(reqs[0].rid)
    assert be.can_admit(extra)
    # a request whose completed footprint exceeds the whole pool is refused
    # even into an empty pool (it could only ever deadlock mid-decode)
    be2 = ModelBackend(model, params, max_len=128, kv_pages=4, page_size=16)
    big = _requests(1, seed=8, prompt=16, out=16)[0]
    big.max_new_tokens = 64                      # 80 tokens = 5 pages > 4
    assert not be2.can_admit(big)


def test_sim_model_admission_parity(model_and_params):
    """Satellite: SimBackend and paged ModelBackend must expose identical
    incremental admission semantics (same pool ⇒ same admit decisions and
    claimed pages), so cluster routing sees one signal for both."""
    model, params = model_and_params
    mb = ModelBackend(model, params, max_len=1 << 10, kv_pages=8,
                      page_size=16)
    sb = SimBackend(CFG, kv_pool_pages=8, page_size=16)
    seq = _requests(6, prompt=30, out=40)        # 2 prompt pages, 5 total
    for r in seq:
        assert mb.can_admit(r) == sb.can_admit(r)
        assert mb.admit_pages(r) == sb.admit_pages(r) == 2
        if mb.can_admit(r):
            mb.admit(r), sb.admit(r)
        assert mb.kv.free_pages == sb.kv.free_pages
    assert mb.kv.free_pages == 0                 # 4 admitted × 2 pages
    big = _requests(1, seed=7, prompt=16, out=1 << 9)[0]
    big.rid = 123
    assert mb.can_admit(big) == sb.can_admit(big) is False   # never fits


# ---------------------------------------------------------------------------
# slot/page recycle hygiene (release → re-admit regression)
# ---------------------------------------------------------------------------

def test_release_readmit_recycles_cleanly(model_and_params):
    """A recycled page set must reproduce exactly what a fresh backend
    produces — no stale page contents or table state."""
    model, params = model_and_params
    a = _requests(1, seed=3, prompt=24, out=16)[0]
    b = _requests(1, seed=4, prompt=8, out=16)[0]
    b.rid = 1

    be = ModelBackend(model, params, n_slots=1, max_len=64)
    _, outs = _run_engine(be, [a], max_batch=1)        # pages used + freed
    _, outs_b = _run_engine(be, [b], max_batch=1)      # pages recycled

    fresh = ModelBackend(model, params, n_slots=1, max_len=64)
    _, outs_fresh = _run_engine(fresh, [b], max_batch=1)
    assert outs_b[b.rid] == outs_fresh[b.rid]


def test_hybrid_slot_release_resets_len():
    """Recurrent-slot families (hybrid) keep the slot cache; releasing a
    slot must zero its context length for the next occupant."""
    cfg = ArchConfig(name="h", family="hybrid", n_layers=4, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                     attn_period=4, attn_offset=1, block_size=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    be = ModelBackend(model, params, n_slots=2, max_len=64)
    assert not be.paged                                # slot path retained
    req = _requests(1, prompt=24)[0]
    be.admit(req)
    slot = be._slot_of[req.rid]
    assert int(be.cache["len"][slot]) == 24
    be.release(req.rid)
    assert int(be.cache["len"][slot]) == 0


def test_release_resets_recurrent_states():
    cfg = ArchConfig(name="r", family="ssm", n_layers=2, d_model=64,
                     rwkv_head_dim=16, d_ff=128, vocab_size=256,
                     diffusion=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    be = ModelBackend(model, params, n_slots=1, max_len=64, decode_mode="ar")
    req = _requests(1, prompt=8)[0]
    be.admit(req)
    slot = be._slot_of[req.rid]
    dirty = any(bool(jnp.any(leaf[:, slot] != 0))
                for leaf in jax.tree.leaves(be.cache["states"]))
    assert dirty                                       # prefill wrote state
    be.release(req.rid)
    for leaf in jax.tree.leaves(be.cache["states"]):
        assert not bool(jnp.any(leaf[:, slot] != 0))


# ---------------------------------------------------------------------------
# cluster admission reads one allocator signal for sim and paged model paths
# ---------------------------------------------------------------------------

def test_cluster_admission_reads_paged_allocator(model_and_params):
    model, params = model_and_params
    be = ModelBackend(model, params, max_len=64, kv_pages=4, page_size=16)
    core = EngineCore(be, FixedScheduler(8), max_batch=8)
    policy = KVAdmissionPolicy(low_watermark=0.0)
    small, big = _requests(2, prompt=16, out=16)       # 1 prompt page each
    big.prompt_len, big.max_new_tokens = 48, 32        # 5 pages > pool
    assert fits_ever(core, small)
    assert not fits_ever(core, big)                    # exceeds whole pool
    assert policy.admissible(core, small)
    be.admit(small)
    assert policy.reserved_pages(core) == 0            # active, not pending
    core.submit(small)                                 # now pending too
    assert policy.reserved_pages(core) == 1            # its prompt page
    # 1 allocated + 1 reserved + 2 more prompt pages fit a 4-page pool, but
    # a third pending one-pager would leave no headroom at watermark 0.25
    tight = KVAdmissionPolicy(low_watermark=0.6)
    small2 = _requests(1, seed=9, prompt=16, out=16)[0]
    small2.rid = 7
    assert policy.admissible(core, small2)
    assert not tight.admissible(core, small2)


def test_build_model_cluster_serves_paged_replicas(model_and_params):
    """Two paged real-model replicas under the cluster event loop, placed
    through the same KVAdmissionPolicy the sim cluster uses."""
    model, params = model_and_params
    cluster = build_model_cluster(model, params, 2, "round_robin",
                                  profile=PROF, mode="bd8", max_len=64,
                                  max_batch=4)
    rep = cluster.run(_requests(6, simultaneous=True))
    assert len(rep.metrics) == 6
    assert all(m.n_tokens == 16 for m in rep.metrics)
    assert not rep.rejected
    for core in cluster.replicas:
        assert core.backend.kv.free_pages == core.backend.kv.n_pages


class _HBMDevice:
    """A device that reports an HBM limit the way a TPU's memory_stats()
    does, with the bytes in use read from the live arrays."""

    def __init__(self, device, limit):
        self._device, self._limit = device, limit

    def __getattr__(self, name):
        return getattr(self._device, name)

    def memory_stats(self):
        return {"bytes_limit": self._limit,
                "bytes_in_use": sum(a.nbytes for a in jax.live_arrays())}


def test_build_model_cluster_splits_hbm_between_replicas(model_and_params,
                                                         monkeypatch):
    """Replicas on one device split the HBM left after the weights; the
    first one built does not take it all."""
    from repro.serving.backends import POOL_HEADROOM_BYTES
    model, params = model_and_params
    page_bytes = 2 * CFG.n_layers * CFG.kv_page_size * CFG.n_kv_heads \
        * CFG.hd * CFG.cdt.itemsize
    gc.collect()
    in_use = sum(a.nbytes for a in jax.live_arrays())
    limit = in_use + POOL_HEADROOM_BYTES + 2 * 40 * page_bytes + page_bytes
    dev = _HBMDevice(jax.devices()[0], limit)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])
    cluster = build_model_cluster(model, params, 2, "round_robin",
                                  profile=PROF, mode="bd8", max_len=64,
                                  max_batch=4)
    assert [core.backend.kv.n_pages for core in cluster.replicas] == [40, 40]
    assert dev.memory_stats()["bytes_in_use"] <= limit - POOL_HEADROOM_BYTES
    rep = cluster.run(_requests(4, simultaneous=True))
    assert all(m.n_tokens == 16 for m in rep.metrics)


def test_fits_ever_respects_model_max_len(model_and_params):
    model, params = model_and_params
    be = ModelBackend(model, params, max_len=32, kv_pages=64)
    core = EngineCore(be, FixedScheduler(8))
    req = _requests(1, prompt=24, out=16)[0]           # 40 tokens > max_len
    assert not fits_ever(core, req)                    # pages OK, ctx not

"""Device-resident decode hot path: fused chunk+freeze+sample step.

Pins (1) the donation contract — the page pool aliases input→output in the
compiled HLO (no per-step full-pool copy) and stale handles raise instead
of silently reading freed memory; (2) fused-vs-host sampling equivalence —
the on-device fp32 softmax-confidence/argmax commits bit-identical tokens
to a shadow reference (separate non-fused chunk-forward + host fp64
sampling, the retired pre-fusion path re-derived in-test) at every
dispatch, across slide / OBS / block-pinned windows and AR decode; (3) the
batched window assembly matches the per-request scalar state machine."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FixedScheduler
from repro.core.chunked import (ChunkedDecodeState, batch_apply_step,
                                batch_windows, freeze_run)
from repro.core.diffusion import commit_decisions, softmax_confidence
from repro.kernels.ops import softmax_confidence_op
from repro.models import ArchConfig, build_model
from repro.serving import (DATASETS, ModelBackend, PoissonWorkload,
                           ServingEngine)

CFG = ArchConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                 n_kv_heads=2, d_ff=128, vocab_size=256, block_size=8,
                 confidence_threshold=0.6)
PROF = DATASETS["sharegpt"]


@pytest.fixture(scope="module")
def model_and_params():
    model = build_model(CFG)
    return model, model.init(jax.random.PRNGKey(0))


def _requests(n, seed=0, prompt=12, out=16):
    rng = np.random.default_rng(seed)
    reqs = list(PoissonWorkload(PROF, 50.0, n, seed=seed))
    for r in reqs:
        r.prompt_len = prompt
        r.max_new_tokens = out
        r.prompt_tokens = rng.integers(4, CFG.vocab_size, prompt).tolist()
    return reqs


def _attach_shadow(be, model):
    """Shadow-check every fused dispatch: recompute the window logits with
    a separate non-fused (non-donating) ``chunk_forward_paged`` jit, sample
    on the host in fp64 (``softmax_confidence``), and require the fused
    on-device sampling to return identical tokens at valid positions — the
    retired pre-fusion path, re-derived in-test as a golden."""
    ref_chunk = jax.jit(functools.partial(
        model.chunk_forward_paged, impl="ref", interpret=True))
    orig = be._decode_paged
    checked = {"n": 0}

    def wrapped(params, cache, w, s, v, tables, ctx, a, **kw):
        logits, _ = ref_chunk(params, cache, w, s, v, tables, ctx)
        conf_h, tok_h = softmax_confidence(np.asarray(logits, np.float64))
        conf, tok, pages = orig(params, cache, w, s, v, tables, ctx, a, **kw)
        # vocab-free return traffic: conf fp32 + tok int32 = 8 B per window
        # slot (the logits path moved 4·V per slot)
        assert conf.nbytes + tok.nbytes == 8 * w.shape[0] * w.shape[1]
        valid = np.arange(w.shape[1])[None, :] < np.asarray(v)[:, None]
        np.testing.assert_array_equal(
            np.where(valid, np.asarray(tok), 0), np.where(valid, tok_h, 0))
        np.testing.assert_allclose(
            np.where(valid, np.asarray(conf), 0.0),
            np.where(valid, conf_h, 0.0), rtol=1e-5, atol=1e-6)
        checked["n"] += 1
        return conf, tok, pages

    be._decode_paged = wrapped
    return checked


def _run(model, params, mode="elastic", chunk=8, obs=False, n=6,
         attn_impl="ref", shadow=False):
    be = ModelBackend(model, params, n_slots=8, max_len=64, decode_mode=mode,
                      obs=obs, attn_impl=attn_impl)
    checked = _attach_shadow(be, model) if shadow else None
    eng = ServingEngine(be, FixedScheduler(chunk), max_batch=8)
    outs = {}
    orig = be.release

    def spy(rid):
        outs[rid] = be.state(rid).output_tokens
        orig(rid)

    be.release = spy
    rep = eng.run(_requests(n))
    return rep, outs, be, checked


# ---------------------------------------------------------------------------
# fused vs host sampling: engine-level teacher-forced goldens
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,chunk,obs", [("elastic", 8, False),
                                            ("elastic", 4, False),
                                            ("elastic", 8, True),
                                            ("ar", 1, False)])
def test_fused_step_commits_identical_tokens(model_and_params, mode, chunk,
                                             obs):
    """The fused device step (on-device fp32 sampling, single dispatch,
    donated pool) must commit exactly the tokens host fp64 sampling over
    full reference logits commits — checked at EVERY dispatch by the
    shadow hook, so a single divergent argmax anywhere in the run fails."""
    model, params = model_and_params
    rep, outs, be, checked = _run(model, params, mode, chunk, obs,
                                  shadow=True)
    assert checked["n"] == be.decode_dispatches > 0
    assert len(outs) == 6 and all(len(v) > 0 for v in outs.values())
    assert rep.total_tokens == sum(len(v) for v in outs.values())


def test_fused_is_one_dispatch_per_step(model_and_params):
    """Steady-state fused decode issues exactly ONE device dispatch per
    engine iteration (chunk-forward + freeze + sample fused — the
    pre-fusion chunk/freeze pair issued two), and the per-device counter
    view stays consistent with the logical one."""
    model, params = model_and_params
    be = ModelBackend(model, params, n_slots=8, max_len=64, decode_mode="ar",
                      attn_impl="ref")
    ticks = []
    orig = be.decode_step

    def spy(rids, chunk):
        before = be.decode_dispatches
        infos = orig(rids, chunk)
        live = [r for r in rids if not be._prefill.pending(r)
                and not be.state(r).done]
        ticks.append((len(live), be.decode_dispatches - before))
        return infos

    be.decode_step = spy
    ServingEngine(be, FixedScheduler(1), max_batch=8).run(_requests(3))
    assert any(n for n, _ in ticks)
    # every tick with a live decodable batch = exactly one fused dispatch
    assert all(d == 1 for n, d in ticks if n)
    # unsharded pool: device dispatches == logical dispatches
    assert be.device_dispatches == \
        be.decode_dispatches + be.prefill_dispatches
    assert be.collective_bytes == 0


# ---------------------------------------------------------------------------
# op-level equivalence (covers block-pinned windows, ties, padded rows)
# ---------------------------------------------------------------------------

def test_device_sampling_matches_fp64_host_on_model_logits(model_and_params):
    """On real (teacher-forced) model logits across slide and block-pinned
    window shapes, the device op must reproduce the host argmax exactly and
    the confidence to fp32 accuracy."""
    model, params = model_and_params
    rng = np.random.default_rng(0)
    B, T = 4, 16
    toks = rng.integers(4, CFG.vocab_size, (B, T))
    for mask_mode in ("block_causal", "causal"):
        logits = model.apply(params, jnp.asarray(toks, jnp.int32),
                             mask_mode=mask_mode)
        conf_h, tok_h = softmax_confidence(np.asarray(logits))
        conf_d, tok_d = softmax_confidence_op(logits)
        np.testing.assert_array_equal(np.asarray(tok_d), tok_h)
        np.testing.assert_allclose(np.asarray(conf_d), conf_h,
                                   rtol=1e-6, atol=1e-7)


def test_device_sampling_breaks_ties_like_host():
    """Exact argmax ties must resolve to the first maximal index on both
    paths (numpy and XLA argmax both pick the first occurrence)."""
    logits = np.zeros((3, 8), np.float32)
    logits[0, [2, 5]] = 3.0              # tie → index 2
    logits[1, :] = 1.0                   # all tied → index 0
    logits[2, [0, 7]] = -1.0
    logits[2, [3, 4]] = 2.5              # tie → index 3
    conf_h, tok_h = softmax_confidence(logits)
    conf_d, tok_d = softmax_confidence_op(jnp.asarray(logits))
    np.testing.assert_array_equal(np.asarray(tok_d), tok_h)
    assert list(tok_h) == [2, 0, 3]


# ---------------------------------------------------------------------------
# donation: HLO input/output aliasing + no use-after-donate
# ---------------------------------------------------------------------------

def test_fused_step_hlo_aliases_page_pool(model_and_params):
    """The compiled fused step must alias the page-pool inputs onto its
    outputs (XLA updates the pool in place) — otherwise every decode step
    materializes a full copy of the KV pool."""
    from repro.analysis.rules import check_pool_donation

    model, params = model_and_params
    be = ModelBackend(model, params, max_len=64, attn_impl="ref")
    B, c, W = 2, 4, be._table_width
    cache = be._pages_cache()
    lowered = be._decode_paged.lower(
        params, cache, jnp.zeros((B, c), jnp.int32),
        jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
        jnp.zeros((B, W), jnp.int32), jnp.zeros(B, jnp.int32),
        jnp.zeros(B, jnp.int32))
    # both pool buffers (k_pages, v_pages) alias through: the shared
    # HLO001 rule returns no findings
    txt = lowered.compile().as_text()
    assert check_pool_donation(txt, target="decode_step_paged") == []
    pool_bytes = cache["k_pages"].nbytes
    # sanity: aliasing parsed from a module that actually owns the pool
    assert pool_bytes > 0
    # prefill donates the pool too
    toks = jnp.zeros((B, 8), jnp.int32)
    lowered = be._prefill_paged.lower(
        params, be._pages_cache(), toks, jnp.zeros(B, jnp.int32),
        jnp.zeros((B, W), jnp.int32))
    txt = lowered.compile().as_text()
    assert check_pool_donation(txt, target="prefill_paged") == []


def test_serving_programs_are_named(model_and_params):
    """The paged programs are jitted under their model methods' names, so
    HLO dumps and profiler traces name them (not ``jit__unknown``)."""
    model, params = model_and_params
    be = ModelBackend(model, params, max_len=64, attn_impl="ref")
    B, W = 2, be._table_width
    z = jnp.zeros(B, jnp.int32)
    tables = jnp.zeros((B, W), jnp.int32)
    lowered = {
        "decode_step_paged": be._decode_paged.lower(
            params, be._pages_cache(), jnp.zeros((B, 4), jnp.int32), z, z,
            tables, z, z),
        "prefill_chunk_paged": be._prefill_chunk.lower(
            params, be._pages_cache(), jnp.zeros((B, 8), jnp.int32), z, z,
            tables),
        "prefill_paged": be._prefill_paged.lower(
            params, be._pages_cache(), jnp.zeros((B, 8), jnp.int32), z,
            tables)}
    for name, low in lowered.items():
        assert low.as_text().startswith(f"module @jit_{name} "), name


def test_no_use_after_donate_on_retained_pages_reference(model_and_params):
    """A stale handle to the pre-step pool must raise (buffer deleted), and
    the backend itself must never hold one: after every decode step the
    allocator's pool handles are the step's outputs and remain readable."""
    model, params = model_and_params
    be = ModelBackend(model, params, max_len=64, attn_impl="ref")
    req = _requests(1)[0]
    be.admit(req)
    stale_k, stale_v = be.kv.k_pages, be.kv.v_pages
    be.decode_step([req.rid], 8)         # flushes prefill + fused step
    # the backend's current handles are live and readable
    assert np.asarray(be.kv.k_pages).shape == stale_k.shape
    # the donated pre-step handles were consumed
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(stale_k)
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(stale_v)
    # and decoding continues correctly on the in-place pool to completion
    while not be.state(req.rid).done:
        be.decode_step([req.rid], 8)
    out = be.state(req.rid).output_tokens
    assert len(out) == req.max_new_tokens or CFG.mask_token_id not in out


# ---------------------------------------------------------------------------
# batched window/apply helpers vs the scalar state machine
# ---------------------------------------------------------------------------

def _mk_state(rng, prompt, gen, obs=False, threshold=0.6, eos=None):
    st = ChunkedDecodeState(prompt_len=prompt, max_new_tokens=gen,
                            block_size=8, threshold=threshold, mask_token=3,
                            eos_token=eos, obs=obs)
    # randomly pre-commit/advance to land in a mid-decode configuration
    for _ in range(rng.integers(0, 4)):
        toks, start, valid, cai = st.window(int(rng.integers(1, 9)))
        if valid == 0:
            break
        conf = rng.random(len(toks))
        tok = rng.integers(5, 100, len(toks))
        _, n_adv = st.apply_step(conf, tok, valid, cai)
        st.advance(n_adv)
    return st


def test_batch_windows_matches_scalar_window():
    rng = np.random.default_rng(0)
    states = [_mk_state(rng, int(rng.integers(0, 20)),
                        int(rng.integers(4, 24)), obs=bool(rng.integers(2)))
              for _ in range(12)]
    for chunk in (1, 4, 8, 16):
        win, start, valid, cai = batch_windows(states, chunk)
        for i, st in enumerate(states):
            t, s, v, c = st.window(chunk)
            np.testing.assert_array_equal(win[i], t)
            assert (start[i], valid[i]) == (s, v)
            np.testing.assert_array_equal(cai[i], c)


def test_freeze_run_is_precomputable_and_matches_apply_step():
    """freeze_run (computed BEFORE the step — what the fused dispatch
    freezes) must equal the n_advance apply_step reports AFTER committing,
    including EOS-shrunken gen_limits."""
    rng = np.random.default_rng(1)
    for trial in range(50):
        states = [_mk_state(rng, 4, int(rng.integers(4, 20)),
                            eos=7 if trial % 2 else None)
                  for _ in range(6)]
        chunk = int(rng.integers(1, 9))
        win, start, valid, cai = batch_windows(states, chunk)
        pre = freeze_run(valid, cai)
        conf = rng.random((len(states), chunk))
        tok = rng.integers(5, 12, (len(states), chunk))  # often hits eos=7
        _, n_adv = batch_apply_step(states, conf, tok, valid, cai)
        np.testing.assert_array_equal(pre, n_adv)

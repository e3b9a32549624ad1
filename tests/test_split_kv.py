"""Sharded page pool + split-KV paged decode tests.

Pins the tentpole contract of the ``kv``-axis sharded serving path:

* **shard-count invariance** — a ModelBackend serving over a pool striped
  across ``kv_shards ∈ {1, 2, 4}`` commits bit-identical tokens to the
  single-shard run, for slide / OBS / AR decode (the split-KV merge is an
  exact log-sum-exp combine, not an approximation);
* **op-level equivalence** — ``split_kv_paged_partial`` on a 4-shard host
  mesh matches the unsharded paged-attention partial for both the jnp
  oracle and the Pallas kernel (interpret mode);
* **donation survives sharding** — the compiled sharded fused decode step
  still aliases the page-pool inputs onto its outputs per shard;
* **sharded allocator invariants** (hypothesis) — striping is a partition
  of the physical pages (no cross-shard double-booking), every table obeys
  ``shard(page[j]) == (offset + j) % S``, and ``OutOfPages``/``can_admit``
  trigger exactly when the specific shard a slot stripes onto is empty,
  not when aggregate free pages hit zero;
* **flash-partial combine** — ``kernels.ops.combine_flash_partials``
  reproduces full softmax attention from chunked partials (the one shared
  merge the unsharded full op, the ref oracle, and the cross-shard psum
  merge all call).

Multi-device tests run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (same idiom as
``test_sharding_and_analysis``) so the main pytest process keeps its
single-device jax config.
"""

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.kv_pool import OutOfPages, PagedKVAllocator

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + ":" + REPO
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=500)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_kv_shard_rules_spec():
    from repro.distributed.sharding import kv_shard_rules
    r = kv_shard_rules()
    assert r.table["kv_pages"] == "kv"
    assert r.table["kv_seq"] == "kv"          # split-KV decode over kv axis
    spec = r.spec("layers", "kv_pages", None, "kv_heads", "head_dim")
    assert tuple(spec) == (None, "kv", None, None, None)


# ---------------------------------------------------------------------------
# op level: flash-partial combine (the one merge everything shares)
# ---------------------------------------------------------------------------

def test_combine_flash_partials_matches_full_softmax():
    """Chunked (acc, m, l) partials combined with the shared op must equal
    monolithic softmax attention — including an empty partial (l=0, very
    negative m), the shape a shard with no pages for a request produces."""
    from repro.kernels.ops import combine_flash_partials
    rng = np.random.default_rng(0)
    B, c, H, D, T = 2, 3, 4, 8, 32
    q = rng.standard_normal((B, c, H, D)).astype(np.float32)
    k = rng.standard_normal((B, T, H, D)).astype(np.float32)
    v = rng.standard_normal((B, T, H, D)).astype(np.float32)
    s = np.einsum("bchd,bthd->bcht", q, k) / np.sqrt(D)
    full = np.einsum("bcht,bthd->bchd",
                     np.exp(s - s.max(-1, keepdims=True))
                     / np.exp(s - s.max(-1, keepdims=True)).sum(
                         -1, keepdims=True), v)

    def partial(lo, hi):
        sc = s[..., lo:hi]
        m = sc.max(-1)
        p = np.exp(sc - m[..., None])
        return (jnp.asarray(np.einsum("bcht,bthd->bchd", p, v[:, lo:hi])),
                jnp.asarray(m), jnp.asarray(p.sum(-1)))

    parts = [partial(0, 12), partial(12, 32)]
    out = np.asarray(combine_flash_partials(parts))
    np.testing.assert_allclose(out, full, rtol=1e-5, atol=1e-6)
    # an empty shard's partial is a no-op in the merge
    empty = (jnp.zeros((B, c, H, D)), jnp.full((B, c, H), -1e30),
             jnp.zeros((B, c, H)))
    out2 = np.asarray(combine_flash_partials(parts + [empty]))
    np.testing.assert_allclose(out2, full, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# sharded allocator invariants
# ---------------------------------------------------------------------------

def _check_partition(kv: PagedKVAllocator):
    free = [p for f in kv._free for p in f]
    held = [p for t in kv._tables.values() for p in t]
    assert len(free) + len(held) == kv.n_pages          # nothing lost
    assert len(set(free) | set(held)) == kv.n_pages     # nothing doubled
    for s, f in enumerate(kv._free):
        assert all(kv.shard_of(p) == s for p in f)      # home-shard lists
    for rid, t in kv._tables.items():
        o = kv.stripe_offset(rid)
        for j, page in enumerate(t):
            assert kv.shard_of(page) == (o + j) % kv.kv_shards


def test_sharded_allocator_invariants_random_ops():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    st = hyp.strategies

    @settings(max_examples=60, deadline=None)
    @given(shards=st.sampled_from([1, 2, 4]),
           ops=st.lists(st.tuples(st.integers(0, 3), st.integers(1, 200),
                                  st.integers(0, 9)),
                        min_size=1, max_size=60))
    def run(shards, ops):
        kv = PagedKVAllocator(32, page_size=16, kv_shards=shards)
        nxt = 0
        live: list[int] = []
        for op, n_tok, pick in ops:
            if op == 0:                                   # allocate
                fits = kv.can_admit(n_tok)
                try:
                    kv.allocate(nxt, n_tok)
                    assert fits                            # admit said yes
                    live.append(nxt)
                except OutOfPages:
                    assert not fits                        # ...or said no
                nxt += 1
            elif op == 1 and live:                         # extend
                rid = live[pick % len(live)]
                try:
                    kv.extend(rid, kv.length(rid) + n_tok)
                except OutOfPages:
                    pass                                   # transactional
            elif op == 2 and live:                         # trim
                rid = live[pick % len(live)]
                kv.trim(rid, max(kv.length(rid) - n_tok, 1))
            elif op == 3 and live:                         # free
                kv.free(live.pop(pick % len(live)))
            _check_partition(kv)

    run()


def test_out_of_pages_exactly_on_fullest_shard():
    """Aggregate free pages can be positive while a request still cannot
    grow: OutOfPages names the exhausted shard, and is raised iff the
    specific shard a slot stripes onto is empty."""
    kv = PagedKVAllocator(8, page_size=16, kv_shards=4)   # 2 pages/shard
    # rid 0 takes a full stripe round: one page from each shard
    kv.allocate(0, 4 * 16)
    o = kv.stripe_offset(0)
    # drain the shard rid 0's next slot stripes onto via a fresh victim:
    nxt_shard = (o + 4) % 4
    victims = []
    for rid in (1, 2, 3):
        kv.allocate(rid, 16)
        victims.append(rid)
        if kv.shard_free_pages[nxt_shard] == 0:
            break
    assert kv.shard_free_pages[nxt_shard] == 0
    assert kv.free_pages > 0                              # aggregate free!
    with pytest.raises(OutOfPages, match=f"shard {nxt_shard}"):
        kv.extend(0, 5 * 16)
    # freeing a page on that shard makes the same extend succeed
    freed = next(r for r in victims
                 if kv.shard_of(kv.block_table(r)[0]) == nxt_shard)
    kv.free(freed)
    assert len(kv.extend(0, 5 * 16)) == 5
    _check_partition(kv)


def test_single_shard_degenerates_to_flat_allocator():
    """kv_shards=1 reproduces the historical flat allocator bit-for-bit:
    ascending page grants, LIFO reuse, zero stripe offsets."""
    kv = PagedKVAllocator(16, page_size=16, kv_shards=1)
    assert kv.allocate(0, 40) == [0, 1, 2]
    assert kv.extend(0, 70) == [0, 1, 2, 3, 4]
    assert kv.trim(0, 41) == [0, 1, 2]
    assert kv.allocate(1, 1) == [3]                       # LIFO reuse
    assert kv.stripe_offset(0) == kv.stripe_offset(1) == 0
    assert kv.shard_free_pages == [kv.free_pages]
    _check_partition(kv)


# ---------------------------------------------------------------------------
# multi-device: split-KV partial vs unsharded, token invariance, donation
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_split_kv_partial_matches_unsharded_oracle():
    """split_kv_paged_partial on a 4-shard mesh == the unsharded paged
    partial, for both the jnp oracle and the Pallas kernel (interpret)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.serving.kv_pool import PagedKVAllocator
        from repro.distributed.collectives import (KVShardSpec,
                                                   split_kv_paged_partial)
        from repro.launch.mesh import make_kv_mesh
        from repro.kernels.ref import paged_chunk_ref
        from repro.kernels.ops import combine_flash_partials

        S, ps, Pg = 4, 4, 32
        kv = PagedKVAllocator(Pg, ps, kv_shards=S)
        lens = [10, 7, 16, 3]
        for rid, n in enumerate(lens):
            kv.allocate(rid, n)
        rids = list(range(len(lens)))
        tables = jnp.asarray(np.array(kv.batch_tables(rids, width=8)))
        offs = jnp.asarray(kv.stripe_offsets(rids))
        ctx = jnp.asarray(np.array(lens, np.int32))

        B, c, H, KVH, D = len(lens), 2, 4, 2, 8
        q = jax.random.normal(jax.random.PRNGKey(0), (B, c, H, D))
        kp = jax.random.normal(jax.random.PRNGKey(1), (Pg, ps, KVH, D))
        vp = jax.random.normal(jax.random.PRNGKey(2), (Pg, ps, KVH, D))

        want = combine_flash_partials(
            [paged_chunk_ref(q, kp, vp, tables, ctx)])
        ks = KVShardSpec(make_kv_mesh(S), S)
        for impl in ("ref", "kernel"):
            part = split_kv_paged_partial(q, kp, vp, tables, ctx, offs, ks,
                                          impl=impl, interpret=True)
            got = combine_flash_partials([part])
            err = float(jnp.max(jnp.abs(want - got)))
            assert err < 1e-5, (impl, err)
            print(impl, err)
    """)
    assert "ref" in out and "kernel" in out


@pytest.mark.slow
def test_tokens_invariant_across_shard_counts():
    """ModelBackend commits bit-identical tokens for kv_shards ∈ {1, 2, 4}
    across slide (elastic), OBS, and AR decode — the sharded pool is a
    layout change, not a numerics change (exact log-sum-exp merge)."""
    out = _run_subprocess("""
        import numpy as np, jax
        from repro.models.common import ArchConfig
        from repro.models.registry import build_model
        from repro.serving.backends import ModelBackend
        from repro.serving.request import Request

        CFG = ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                         n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                         block_size=8, confidence_threshold=0.6)
        model = build_model(CFG)
        params = model.init(jax.random.PRNGKey(0))

        def run(kv_shards, mode, obs=False, impl="ref"):
            be = ModelBackend(model, params, n_slots=8, max_len=128,
                              decode_mode=mode, obs=obs, attn_impl=impl,
                              kv_shards=kv_shards)
            rng = np.random.default_rng(0)
            rids = []
            for rid in range(3):
                pl = int(rng.integers(5, 30))
                be.admit(Request(
                    rid=rid, arrival_time=0.0, prompt_len=pl,
                    max_new_tokens=16,
                    prompt_tokens=list(map(int,
                                           rng.integers(5, 250, pl)))))
                rids.append(rid)
            for _ in range(64):
                if all(be.state(r).done for r in rids) \\
                        and not be._prefill.queue:
                    break
                be.decode_step(rids, 1 if mode == "ar" else 8)
            return {r: list(be.state(r).committed[:be.state(r).frozen])
                    for r in rids}

        for mode, obs in (("elastic", False), ("elastic", True),
                          ("ar", False)):
            base = run(1, mode, obs)
            assert any(len(v) for v in base.values())
            for S in (2, 4):
                got = run(S, mode, obs)
                assert got == base, (mode, obs, S)
            print("ok", mode, "obs" if obs else "slide")
        # the Pallas kernel path (interpret mode) is shard-invariant too
        assert run(2, "elastic", impl="kernel") == \\
            run(1, "elastic", impl="kernel")
        print("ok kernel")
    """)
    assert out.count("ok") == 4


@pytest.mark.slow
def test_sharded_fused_step_keeps_donation():
    """input_output aliasing (pool donation) must survive the shard_map:
    the scatter is shard-local, so each shard's pool block aliases
    input→output in the compiled sharded fused step."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.models.common import ArchConfig
        from repro.models.registry import build_model
        from repro.serving.backends import ModelBackend
        from repro.analysis.rules import check_pool_donation

        CFG = ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                         n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                         block_size=8, confidence_threshold=0.6)
        model = build_model(CFG)
        params = model.init(jax.random.PRNGKey(0))
        for S in (1, 2):
            be = ModelBackend(model, params, n_slots=8, max_len=128,
                              attn_impl="ref", kv_shards=S)
            B, c, W = 4, 8, be._table_width
            args = (be.params, be._pages_cache(),
                    jnp.zeros((B, c), jnp.int32), jnp.zeros(B, jnp.int32),
                    jnp.zeros(B, jnp.int32),
                    jnp.zeros((B, W), jnp.int32),
                    jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32))
            kw = {"shard_offs": jnp.zeros(B, jnp.int32)} if S > 1 else {}
            txt = be._decode_paged.lower(*args, **kw).compile().as_text()
            # both pool buffers alias through: shared HLO001 rule is green
            fs = check_pool_donation(txt, target=f"decode@kv{S}")
            assert fs == [], (S, [f.message for f in fs])
            print(f"S={S} aliases=ok")
    """)
    assert "S=2" in out

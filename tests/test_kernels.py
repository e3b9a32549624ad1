"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs ref.py
oracles (interpret=True executes the kernel body on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return 2e-5 if dtype == jnp.float32 else 3e-2


# ---------------------------------------------------------------------------
# chunked paged attention
# ---------------------------------------------------------------------------

PAGED_SHAPES = [
    # B, c, H, KVH, D, page_size, n_slots
    (1, 2, 2, 1, 64, 16, 4),
    (2, 8, 4, 2, 64, 16, 8),
    (2, 16, 8, 2, 128, 16, 4),
    (3, 32, 6, 3, 64, 8, 16),
    (2, 1, 4, 4, 128, 32, 2),     # MHA, AR-style single query
    (1, 80, 8, 2, 64, 16, 8),     # 320 rows per KV head: two row tiles
]


@pytest.mark.parametrize("shape", PAGED_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_chunk_attention(shape, dtype):
    B, c, H, KVH, D, ps, n_slots = shape
    P = B * n_slots + 3
    q = jnp.asarray(RNG.normal(size=(B, c, H, D)), dtype)
    kp = jnp.asarray(RNG.normal(size=(P, ps, KVH, D)), dtype)
    vp = jnp.asarray(RNG.normal(size=(P, ps, KVH, D)), dtype)
    tables = jnp.asarray(
        RNG.permutation(P)[:B * n_slots].reshape(B, n_slots), jnp.int32)
    lens = jnp.asarray(RNG.integers(1, ps * n_slots, B), jnp.int32)
    acc, m, l = ops.paged_chunk_attention(q, kp, vp, tables, lens,
                                          interpret=True)
    acc_r, m_r, l_r = ref.paged_chunk_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(m, m_r, rtol=1e-5, atol=1e-4)
    rel = float(jnp.max(jnp.abs(acc - acc_r))) / \
        (float(jnp.max(jnp.abs(acc_r))) + 1e-9)
    assert rel < _tol(dtype), rel
    np.testing.assert_allclose(l, l_r, rtol=_tol(dtype), atol=1e-5)


@pytest.mark.parametrize("c", [1, 80])       # 80·4 rows: two row tiles
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_paged_kernel_reads_stacked_pool_at_layer(layer, c):
    """The kernel reads a stacked [L, P, ps, KVH, D] pool in place at a
    traced layer index, exactly as the oracle reads that layer's slice.
    The layers hold different values, so a wrong layer index fails.  Row 0
    has no context, row 1's table is padded past its context, rows 1 and 2
    share a page."""
    from repro.kernels.chunked_paged_attn import paged_chunk_attention_kernel
    L, H, KVH, D, ps, P = 3, 8, 2, 64, 16, 12
    rng = np.random.default_rng(layer)
    q = jnp.asarray(rng.normal(size=(3, c, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(L, P, ps, KVH, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(L, P, ps, KVH, D)), jnp.float32)
    tables = jnp.asarray([[0, 0, 0, 0],
                          [5, 7, 0, 0],
                          [9, 7, 2, 11]], jnp.int32)
    lens = jnp.asarray([0, 20, 64], jnp.int32)
    acc, m, l = paged_chunk_attention_kernel(q, kp, vp, tables, lens,
                                             jnp.int32(layer),
                                             interpret=True)
    acc_r, m_r, l_r = ref.paged_chunk_ref(q, kp[layer], vp[layer], tables,
                                          lens)
    np.testing.assert_allclose(m, m_r, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(l, l_r, rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(acc, acc_r, rtol=2e-5, atol=2e-5)
    assert not np.allclose(acc, ref.paged_chunk_ref(
        q, kp[(layer + 1) % L], vp[(layer + 1) % L], tables, lens)[0])


def test_paged_decode_step_kernel_matches_ref_per_layer():
    """A fused paged decode step of a 3-layer model whose pool layers hold
    different values: the kernel path, reading the stacked pool by layer
    index, agrees with the oracle path; rolling the pool's layers changes
    the result, so a wrong layer index cannot pass."""
    import jax

    from repro.models.common import ArchConfig
    from repro.models.registry import build_model
    cfg = ArchConfig(name="t", family="dense", n_layers=3, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                     block_size=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    L, KVH, hd = model.paged_kv_dims()
    P, ps, B, c = 16, 8, 3, 8
    rng = np.random.default_rng(7)
    pool = {n: jnp.asarray(rng.normal(size=(L, P, ps, KVH, hd)), jnp.float32)
            for n in ("k_pages", "v_pages")}
    win = jnp.asarray(rng.integers(4, cfg.vocab_size, (B, c)), jnp.int32)
    ctx = jnp.asarray([0, 13, 30], jnp.int32)
    tables = jnp.asarray([[0, 0, 0, 0, 0, 0],
                          [3, 8, 4, 0, 0, 0],
                          [9, 8, 1, 12, 6, 0]], jnp.int32)
    args = (win, ctx, jnp.full((B,), c, jnp.int32), tables, ctx,
            jnp.asarray([1, 2, 3], jnp.int32))

    def step(impl, cache):
        return model.decode_step_paged(params, cache, *args, impl=impl,
                                       interpret=True)

    conf_k, tok_k, cache_k = step("kernel", pool)
    conf_r, tok_r, cache_r = step("ref", pool)
    np.testing.assert_allclose(conf_k, conf_r, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(tok_k, tok_r)
    for n in pool:
        np.testing.assert_allclose(cache_k[n], cache_r[n], rtol=2e-5,
                                   atol=2e-5)
    rolled = {n: jnp.roll(a, 1, axis=0) for n, a in pool.items()}
    assert not np.allclose(step("ref", rolled)[0], conf_r)


def test_paged_combined_matches_contiguous():
    """Full chunk attention (paged partial + window part) must equal plain
    attention over [cache ‖ window]."""
    B, c, H, KVH, D, ps, n_slots = 2, 8, 4, 2, 64, 16, 6
    bs = 16
    P = B * n_slots
    q = jnp.asarray(RNG.normal(size=(B, c, H, D)), jnp.float32)
    kp = jnp.asarray(RNG.normal(size=(P, ps, KVH, D)), jnp.float32)
    vp = jnp.asarray(RNG.normal(size=(P, ps, KVH, D)), jnp.float32)
    tables = jnp.arange(P, dtype=jnp.int32).reshape(B, n_slots)
    lens = jnp.asarray([64, 32], jnp.int32)
    win_k = jnp.asarray(RNG.normal(size=(B, c, KVH, D)), jnp.float32)
    win_v = jnp.asarray(RNG.normal(size=(B, c, KVH, D)), jnp.float32)
    win_pos = lens[:, None] + jnp.arange(c)[None, :]
    win_valid = jnp.asarray([c, c], jnp.int32)
    out = ops.paged_chunk_attention_full(q, kp, vp, tables, lens, win_k,
                                         win_v, win_pos, win_valid,
                                         block_size=bs, interpret=True)
    # contiguous oracle
    from repro.models.layers import block_causal_mask, sdpa_partial
    k_all = kp[tables].reshape(B, n_slots * ps, KVH, D)
    v_all = vp[tables].reshape(B, n_slots * ps, KVH, D)
    S = n_slots * ps
    cmask = (jnp.arange(S)[None, :] < lens[:, None])[:, None, None, :]
    p1 = sdpa_partial(q, k_all, v_all, cmask)
    sm = block_causal_mask(win_pos, win_pos, bs) | jnp.eye(c, dtype=bool)
    p2 = sdpa_partial(q, win_k, win_v, sm[:, None])
    want = ref.combine_ref([p1, p2], jnp.float32)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# block-diffusion flash attention
# ---------------------------------------------------------------------------

BD_SHAPES = [
    # B, T, H, KVH, D, block, q_tile, kv_tile
    (1, 64, 2, 1, 64, 8, 32, 32),
    (2, 128, 4, 2, 64, 32, 64, 64),
    (2, 256, 4, 4, 128, 32, 128, 128),
    (1, 96, 3, 1, 64, 32, 32, 32),
]


@pytest.mark.parametrize("shape", BD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_diffusion_attention(shape, dtype):
    B, T, H, KVH, D, bs, qt, kt = shape
    q = jnp.asarray(RNG.normal(size=(B, T, H, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, T, KVH, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, T, KVH, D)), dtype)
    lens = jnp.asarray(RNG.integers(bs, T + 1, B), jnp.int32)
    out = ops.block_diffusion_attention(q, k, v, lens, block_size=bs,
                                        q_tile=qt, kv_tile=kt,
                                        interpret=True)
    out_r = ref.block_diffusion_ref(q, k, v, lens, block_size=bs)
    for b in range(B):
        L = int(lens[b])
        np.testing.assert_allclose(out[b, :L], out_r[b, :L],
                                   rtol=_tol(dtype), atol=_tol(dtype))


def test_block_diffusion_matches_model_flash():
    """Kernel agrees with the model's XLA flash path (the one the dry-run
    lowers) — ties the kernel to the production semantics."""
    from repro.models.layers import combine_partials, flash_partial
    B, T, H, KVH, D, bs = 2, 128, 4, 2, 64, 32
    q = jnp.asarray(RNG.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, T, KVH, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, T, KVH, D)), jnp.float32)
    lens = jnp.asarray([T, T - 17], jnp.int32)
    out_k = ops.block_diffusion_attention(q, k, v, lens, block_size=bs,
                                          q_tile=64, kv_tile=64,
                                          interpret=True)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    parts = flash_partial(q, k, v, q_pos=pos, k_pos=pos,
                          k_valid=jnp.arange(T)[None] < lens[:, None],
                          kind="block_causal", block_size=bs)
    out_x = combine_partials([parts], jnp.float32)
    for b in range(B):
        L = int(lens[b])
        np.testing.assert_allclose(out_k[b, :L], out_x[b, :L], rtol=2e-5,
                                   atol=2e-5)

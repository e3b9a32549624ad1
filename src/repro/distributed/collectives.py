"""Distributed-optimization collectives.

* :func:`compressed_psum` — int8 stochastic-rounding gradient compression
  for cross-data-axis gradient reduction: per-block scales, quantize →
  psum in int32 → dequantize.  Cuts gradient all-reduce bytes 2× vs bf16
  (4× vs fp32) at the cost of quantization noise that stochastic rounding
  keeps unbiased.  Used via :func:`compressed_grad_sync` under shard_map
  for the FSDP data axes (the collective-bound term of the kimi-1T train
  cell, EXPERIMENTS §Perf cell 2).
* :func:`split_kv_attention` — sequence-parallel decode attention: each
  shard computes flash partials over its KV slice; (m, l, acc) combine
  exactly with pmax/psum.  The pjit path achieves the same via sharding
  constraints (models/layers.flash_partial reductions partition over the
  kv_seq axis); this explicit shard_map form is used where manual control
  is needed (tests document the equivalence).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# int8 stochastic-rounding compressed gradient reduction
# ---------------------------------------------------------------------------

def _quantize_sr(x, rng, block: int = 256):
    """Stochastic-rounding int8 quantization with per-block scales.

    x [N] fp → (q int8 [N], scales fp32 [ceil(N/block)])."""
    n = x.shape[0]
    pad = (-n) % block
    xp = jnp.pad(x.astype(jnp.float32), (0, pad)).reshape(-1, block)
    scale = jnp.max(jnp.abs(xp), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    y = xp / scale
    lo = jnp.floor(y)
    frac = y - lo
    u = jax.random.uniform(rng, y.shape)
    q = lo + (u < frac)                          # unbiased rounding
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale[:, 0]


def _dequantize(q, scale, n, block: int = 256):
    x = q.astype(jnp.float32).reshape(-1, block) * scale[:, None]
    return x.reshape(-1)[:n]


def compressed_psum(x, axis_name, rng, block: int = 256):
    """psum of ``x`` over ``axis_name`` with int8 payload.

    Each participant quantizes with stochastic rounding; int32 psum of the
    int8 payloads (exact) + fp32 psum of the tiny per-block scales — the
    result is the sum of the participants' dequantized values, unbiased in
    expectation.  Payload: 1 byte/elem + 4/block ≈ 2× cheaper than bf16."""
    n = x.size
    flat = x.reshape(-1)
    q, scale = _quantize_sr(flat, rng, block)
    # sum of per-shard (q_i * scale_i): transmit q*1B; scales are negligible.
    # To keep the reduction exact we psum q_i scaled into a shared grid:
    # use the max scale across shards so int32 accumulation is lossless.
    smax = jax.lax.pmax(scale, axis_name)
    ratio = scale / smax                          # ≤ 1
    qs = jnp.round(q.astype(jnp.float32).reshape(-1, block)
                   * ratio[:, None]).astype(jnp.int32)
    total = jax.lax.psum(qs, axis_name)
    out = (total.astype(jnp.float32) * smax[:, None]).reshape(-1)[:q.size]
    return out[:n].reshape(x.shape).astype(x.dtype)


def compressed_grad_sync(grads, mesh, data_axes, rng, block: int = 256):
    """Tree-map compressed_psum over a gradient pytree under shard_map.

    Grads are assumed replicated over ``data_axes`` *per microbatch partial*
    (pre-reduction); the result equals the cross-data psum up to int8
    stochastic-rounding noise."""
    axis = data_axes if isinstance(data_axes, str) else data_axes[0]

    leaves, treedef = jax.tree.flatten(grads)
    rngs = jax.random.split(rng, len(leaves))

    def one(g, r):
        fn = jax.shard_map(
            functools.partial(compressed_psum, axis_name=axis, rng=r,
                              block=block),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
        return fn(g)

    return treedef.unflatten([one(g, r) for g, r in zip(leaves, rngs)])


# ---------------------------------------------------------------------------
# explicit split-KV decode attention (sequence parallel)
# ---------------------------------------------------------------------------

def _split_kv_body(q, k, v, klen, *, axis_name, scale):
    """Per-shard flash partial over the local KV slice + exact combine."""
    S_loc = k.shape[1]
    shard = jax.lax.axis_index(axis_name)
    base = shard * S_loc
    pos = base + jnp.arange(S_loc)[None, :]                    # [1, S_loc]
    mask = (pos < klen[:, None])[:, None, None, :]             # [B,1,1,S]
    from repro.kernels.ops import combine_flash_partials
    from repro.models.layers import sdpa_partial
    part = sdpa_partial(q, k, v, mask, scale=scale)
    return combine_flash_partials([part], out_dtype=q.dtype,
                                  axis_name=axis_name)


def split_kv_attention(q, k_cache, v_cache, kv_lens, mesh, *,
                       seq_axis: str = "model", scale: float | None = None):
    """q [B,c,H,D] (replicated over seq_axis), KV cache [B,S,KVH,D] sharded
    on S over ``seq_axis`` → exact attention output [B,c,H,D]."""
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    body = functools.partial(_split_kv_body, axis_name=seq_axis, scale=scale)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, seq_axis, None, None),
                  P(None, seq_axis, None, None), P()),
        out_specs=P(), check_vma=False)
    return fn(q, k_cache, v_cache, kv_lens)


# ---------------------------------------------------------------------------
# split-KV paged decode attention (sharded page pool)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class KVShardSpec:
    """Static description of a sharded page pool.

    ``mesh`` must carry ``axis`` with size ``n_shards``; the pool's page
    dim is block-sharded over it (shard *s* physically owns global pages
    ``[s·P/S, (s+1)·P/S)``), while the allocator stripes each request's
    *table slots* round-robin from a per-request offset: global table slot
    ``j`` of a request with stripe offset ``o`` lives on shard
    ``(o + j) % S`` (see ``PagedKVAllocator``).  That strict striping is
    what lets every shard derive its local table, local page indices and
    local context length on device from the replicated global table — no
    per-shard host-side tables cross PCIe.
    """
    mesh: object
    n_shards: int
    axis: str = "kv"


def _local_slots(tables, ctx_lens, stripe_offs, shard, *, n_shards,
                 pages_local, page_size):
    """Shard-local view of the replicated global block tables.

    For shard ``s``, local slot ``i`` holds the request's global table slot
    ``j = i·S + (s - o) % S`` — ascending in ``j``, so every local slot
    before the request's (single, final) partial page is a FULL page and
    the kernel's contiguous ``pos < ctx`` masking stays valid after the
    shard-local reorder.  Returns (local_tables [B, Wl] int32 local page
    ids clipped in-bounds, local_ctx [B] int32 valid tokens on this shard).
    """
    B, W = tables.shape
    S = n_shards
    Wl = -(-W // S)
    d = (shard - stripe_offs) % S                         # [B]
    j = jnp.arange(Wl)[None, :] * S + d[:, None]          # [B, Wl] global slot
    gl = jnp.take_along_axis(tables, jnp.minimum(j, W - 1), axis=1)
    local = jnp.clip(gl - shard * pages_local, 0, pages_local - 1)
    # tokens contributed by global slot j: ps for full pages, the tail for
    # the request's last page, 0 past the context (incl. clamped j ≥ W —
    # ctx ≤ W·ps always, so those slots mask themselves)
    tok = jnp.clip(ctx_lens[:, None] - j * page_size, 0, page_size)
    local_ctx = jnp.sum(tok, axis=1)
    return local.astype(jnp.int32), local_ctx.astype(jnp.int32)


def split_kv_paged_partial(q, k_pages, v_pages, block_tables, ctx_lens,
                           stripe_offs, ks: KVShardSpec, *,
                           interpret: bool, impl: str = "kernel",
                           scale: float | None = None):
    """Split-KV chunked paged attention across ``ks.axis``.

    q [B,c,H,D] replicated; k/v_pages [P,ps,KVH,D] page-dim-sharded;
    block_tables [B,W] GLOBAL page ids (replicated, strict striping per
    :class:`KVShardSpec`); ctx_lens/stripe_offs [B].  Each shard runs
    ``paged_chunk_attention_kernel`` (or the jnp oracle) over its local
    pages only, then the flash partials merge exactly across shards
    (``merge_flash_partials`` pmax/psum).  Returns the *merged partial*
    ``(acc [B,c,H,D] fp32, m [B,c,H], l [B,c,H])`` replicated — the same
    contract as the unsharded kernel, so the caller combines it with the
    in-window partial unchanged.
    """
    P_g, ps = k_pages.shape[0], k_pages.shape[1]
    P_loc = P_g // ks.n_shards

    def body(q_, kp, vp, tables, ctx, offs):
        shard = jax.lax.axis_index(ks.axis)
        lt, lctx = _local_slots(tables, ctx, offs, shard,
                                n_shards=ks.n_shards, pages_local=P_loc,
                                page_size=ps)
        if impl == "ref":
            from repro.kernels import ref
            part = ref.paged_chunk_ref(q_, kp, vp, lt, lctx, scale=scale)
        else:
            from repro.kernels.chunked_paged_attn import \
                paged_chunk_attention_kernel
            part = paged_chunk_attention_kernel(
                q_, kp, vp, lt, lctx, scale=scale, interpret=interpret)
        from repro.kernels.ops import merge_flash_partials
        return merge_flash_partials([part], axis_name=ks.axis)

    fn = jax.shard_map(
        body, mesh=ks.mesh,
        in_specs=(P(), P(ks.axis), P(ks.axis), P(), P(), P()),
        out_specs=(P(), P(), P()), check_vma=False)
    return fn(q, k_pages, v_pages, block_tables.astype(jnp.int32),
              ctx_lens.astype(jnp.int32), stripe_offs.astype(jnp.int32))


def scatter_pages_sharded(pages, new, dest, ks: KVShardSpec):
    """Sharded counterpart of the models' token-granular page scatter.

    pages [L,P,ps,KVH,hd] page-dim-sharded over ``ks.axis``; new
    [L,B,T,KVH,hd] and flat global dest [B,T] replicated.  Each shard
    rewrites ``dest`` into its local flat index (out-of-shard and sentinel
    entries → local OOB, dropped), so the scatter stays shard-local —
    no cross-shard traffic, and XLA can alias the pool buffers per shard
    (the donation contract the fused decode step asserts on its HLO).
    """
    L, P_g, ps, KVH, hd = pages.shape
    P_loc = P_g // ks.n_shards

    def body(pg, new_, dest_):
        shard = jax.lax.axis_index(ks.axis)
        base = shard * P_loc * ps
        d = dest_ - base
        d = jnp.where((d >= 0) & (d < P_loc * ps), d, P_loc * ps)
        flat = pg.reshape(L, P_loc * ps, KVH, hd)
        flat = flat.at[:, d.reshape(-1)].set(
            new_.astype(pg.dtype).reshape(L, -1, KVH, hd), mode="drop")
        return flat.reshape(L, P_loc, ps, KVH, hd)

    fn = jax.shard_map(
        body, mesh=ks.mesh,
        in_specs=(P(None, ks.axis), P(), P()),
        out_specs=P(None, ks.axis), check_vma=False)
    return fn(pages, new, dest)

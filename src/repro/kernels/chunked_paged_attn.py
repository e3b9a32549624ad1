"""Pallas TPU kernel: chunked paged attention (the paper's custom kernel §6).

Serves a variable-length *chunk* of query tokens per request against the
paged KV cache: Q [B, c, H, D] vs pages [P, page_size, KVH, D] indirected
through per-request block tables.  This is the TPU-native adaptation of the
paper's Triton paged-attention kernel:

* the pool is read in place as the model stacks it, [L, P, page_size, KVH,
  D], with the layer as the first scalar-prefetch operand: the scanned layer
  stack hands the kernel the whole pool and its own layer index, so no
  per-layer [P, page_size, KVH, D] slice is ever materialised (a 4-D pool is
  one layer, reshaped for free);
* the grid is (batch, row_tile, page_slot); page indirection happens in the
  BlockSpec ``index_map`` via scalar-prefetched block tables (the TPU
  equivalent of the warp-level gather on GPU), so each step DMAs one whole
  page — every KV head of it — into VMEM.  The block's two minor dims are
  the pool's full ``(KVH, D)``, which is what the TPU tiling rules accept;
  the kernel loops over the KV heads statically;
* GQA is handled by folding the q-heads-per-kv-head group into the row
  dimension of the q tile ([G·c, D] per KV head), keeping the MXU matmul
  dense;
* the rows are tiled (``ROW_TILE``), so VMEM stays bounded for any chunk
  length — a decode window of c ≤ 32 is one tile, an 8192-token prefill
  chunk is ``4·8192 / ROW_TILE`` tiles that each walk the pages;
* slots past a row's context re-use the last live page's block index, so
  the pipeline fetches nothing for the padded width of the table;
* online-softmax state (m, l, acc) lives in fp32 VMEM scratch across the
  sequential page-slot grid dimension;
* the kernel emits flash partials (acc, m, l) so the caller can combine them
  exactly with the in-window bidirectional part (and, under sequence
  parallelism, with other shards' partials).

Validated on CPU via ``interpret=True`` against ``ref.paged_chunk_ref``, and
compiled for v5e in ``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# query rows (G·c) per grid step: bounds the q/acc tiles and the fp32
# scratch to ~11 MiB of VMEM at KVH=8, D=128 — inside v5e's 16 MiB scoped
# default for every chunk length the prefill scheduler can emit
ROW_TILE = 256


def _kernel(layer_ref, tables_ref, lens_ref,   # scalar prefetch (SMEM)
            q_ref, k_ref, v_ref,                # VMEM tiles
            o_ref, m_ref, l_ref,                # outputs
            acc_sc, m_sc, l_sc,                 # VMEM scratch
            *, page_size: int, n_slots: int, n_kv_heads: int, scale: float):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    ctx_len = lens_ref[b]
    base = i * page_size

    @pl.when(base < ctx_len)
    def _work():
        R = q_ref.shape[1]
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (R, page_size), 1)
        valid = pos < ctx_len
        for h in range(n_kv_heads):
            q = q_ref[h].astype(jnp.float32)              # [R, D]
            k = k_ref[:, h, :].astype(jnp.float32)        # [ps, D]
            v = v_ref[:, h, :]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s * scale, NEG_INF)

            m_prev = m_sc[h, :, :1]                       # [R, 1]
            l_prev = l_sc[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            e = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_new = l_prev * corr + jnp.sum(e, axis=1, keepdims=True)
            pv = jax.lax.dot(e.astype(v.dtype), v,
                             preferred_element_type=jnp.float32)
            acc_sc[h] = acc_sc[h] * corr + pv
            m_sc[h] = jnp.broadcast_to(m_new, m_sc.shape[1:])
            l_sc[h] = jnp.broadcast_to(l_new, l_sc.shape[1:])

    @pl.when(i == n_slots - 1)
    def _emit():
        o_ref[...] = acc_sc[...].astype(o_ref.dtype)
        m_ref[...] = m_sc[:, :, :1].astype(m_ref.dtype)
        l_ref[...] = l_sc[:, :, :1].astype(l_ref.dtype)


def paged_chunk_attention_kernel(q, k_pages, v_pages, block_tables, ctx_lens,
                                 layer=None, *, scale: float | None = None,
                                 interpret: bool = False):
    """Raw kernel invocation.

    q [B, c, H, D]; k_pages/v_pages [L, P, page_size, KVH, D] with ``layer``
    an int32 scalar (may be traced) picking the layer read, or one layer's
    [P, page_size, KVH, D] with ``layer`` None; block_tables [B, n_slots]
    int32 (entries must be valid page indices — pad with 0); ctx_lens [B]
    int32.
    Returns flash partials: acc [B,c,H,D] fp32, m/l [B,c,H].
    """
    if k_pages.ndim == 4:
        assert layer is None, "a 4-D pool is one layer"
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    B, c, H, D = q.shape
    page_size, KVH = k_pages.shape[2:4]
    G = H // KVH
    R = G * c
    n_slots = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    # one tile when the rows fit (a block equal to the full dim is legal
    # at any size); otherwise ROW_TILE-row tiles over zero-padded rows
    rt = R if R <= ROW_TILE else ROW_TILE
    Rp = -(-R // rt) * rt

    # group q rows per kv head: [B, KVH, G*c, D]
    qg = q.reshape(B, c, KVH, G, D).transpose(0, 2, 3, 1, 4) \
        .reshape(B, KVH, R, D)
    if Rp != R:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Rp - R), (0, 0)))

    kernel = functools.partial(_kernel, page_size=page_size,
                               n_slots=n_slots, n_kv_heads=KVH, scale=scale)
    grid = (B, Rp // rt, n_slots)

    def page_map(b, r, i, lyr, t, ln):
        # clamp to the row's last live slot: an unchanged block index
        # between steps skips the page fetch
        last = jnp.maximum((ln[b] + page_size - 1) // page_size - 1, 0)
        return (lyr[0], t[b, jnp.minimum(i, last)], 0, 0, 0)

    def row_map(b, r, i, lyr, t, ln):
        return (b, 0, r, 0)

    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, KVH, rt, D), row_map),
                pl.BlockSpec((None, None, page_size, KVH, D), page_map),
                pl.BlockSpec((None, None, page_size, KVH, D), page_map),
            ],
            out_specs=[
                pl.BlockSpec((None, KVH, rt, D), row_map),
                pl.BlockSpec((None, KVH, rt, 1), row_map),
                pl.BlockSpec((None, KVH, rt, 1), row_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((KVH, rt, D), jnp.float32),
                pltpu.VMEM((KVH, rt, 128), jnp.float32),
                pltpu.VMEM((KVH, rt, 128), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, KVH, Rp, D), jnp.float32),
            jax.ShapeDtypeStruct((B, KVH, Rp, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, KVH, Rp, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="chunked_paged_attn",
    )(layer, block_tables, ctx_lens, qg, k_pages, v_pages)

    # ungroup: [B, KVH, G, c, D] → [B, c, H, D] partials
    acc = acc[:, :, :R].reshape(B, KVH, G, c, D).transpose(0, 3, 1, 2, 4) \
        .reshape(B, c, H, D)
    m = m[:, :, :R].reshape(B, KVH, G, c).transpose(0, 3, 1, 2) \
        .reshape(B, c, H)
    l = l[:, :, :R].reshape(B, KVH, G, c).transpose(0, 3, 1, 2) \
        .reshape(B, c, H)
    return acc, m, l

"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` resolves through :func:`resolve_interpret` — the one
place the repository decides between a compiled kernel and Pallas
interpret mode — so the same call sites work in CPU tests and on the chip.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.block_diffusion_attn import block_diffusion_attention_kernel
from repro.kernels.chunked_paged_attn import paged_chunk_attention_kernel


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Pallas interpret mode for a kernel call: an explicit value wins;
    ``None`` compiles the kernel on a TPU backend and interprets it on any
    other (CPU tests).  Every kernel call site of the models, the split-KV
    collectives and the wrappers below resolves through here."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def merge_flash_partials(parts, axis_name=None):
    """Merge flash partials ``[(acc, m, l), ...]`` over disjoint KV sets
    into ONE partial ``(acc, m, l)`` — the exact log-sum-exp combine
    (``acc = Σ e^{logit-m} v``, ``m = max logit``, ``l = Σ e^{logit-m}``).

    With ``axis_name`` the merge additionally reduces across that mapped
    axis (``pmax`` for m, ``psum`` for acc/l) — the cross-shard half of
    split-KV attention under ``shard_map``.  The result is itself a valid
    flash partial, so sharded paged-prefix partials can be merged across
    shards first and then combined with the in-window partial downstream
    without any loss of exactness.
    """
    m_g = parts[0][1]
    for _, m, _ in parts[1:]:
        m_g = jnp.maximum(m_g, m)
    if axis_name is not None:
        m_g = jax.lax.pmax(m_g, axis_name)
    acc_g = 0.0
    l_g = 0.0
    for acc, m, l in parts:
        corr = jnp.exp(m - m_g)
        acc_g = acc_g + acc * corr[..., None]
        l_g = l_g + l * corr
    if axis_name is not None:
        acc_g = jax.lax.psum(acc_g, axis_name)
        l_g = jax.lax.psum(l_g, axis_name)
    return acc_g, m_g, l_g


def combine_flash_partials(parts, out_dtype=jnp.float32, axis_name=None):
    """Normalize the merge of flash partials: ``merge → acc / max(l, ε)``.

    The single shared combine used by the models' paged-prefix path
    (``models/layers.combine_partials``), the kernel oracle
    (``kernels/ref.combine_ref``) and the split-KV collectives
    (``distributed/collectives``) — one implementation so the exactness
    argument (disjoint-KV partials combine associatively) is pinned once.
    """
    acc_g, _, l_g = merge_flash_partials(parts, axis_name=axis_name)
    return (acc_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(out_dtype)


def softmax_confidence_device(logits):
    """On-device argmax + softmax top-probability: logits [..., V] →
    (confidence [...] fp32, token [...] int32).

    The device half of the fused decode step: instead of shipping the full
    ``[B, c, V]`` logits to the host for fp64 ``softmax_confidence``, the
    argmax and its softmax probability are reduced on device and only
    ``2·B·c`` scalars cross PCIe.  Argmax over logits equals argmax over
    softmax probabilities (monotone map), and both XLA and numpy break ties
    at the first maximal index, so committed tokens are bit-identical to
    the host path; confidence is fp32 (vs fp64 on host), which only matters
    when a confidence lands within float error of the commit threshold.
    Traceable — call inside a jitted step (``decode_step_paged``) or via
    the jitted wrapper below.
    """
    x = logits.astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    tok = jnp.argmax(x, axis=-1).astype(jnp.int32)
    conf = jnp.take_along_axis(p, tok[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    return conf, tok


softmax_confidence_op = jax.jit(softmax_confidence_device)


@partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_chunk_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                          scale=None, interpret=None):
    """Flash partials of chunk queries vs the paged prefix cache.

    Returns (acc [B,c,H,D] fp32, m [B,c,H], l [B,c,H]); combine with the
    in-window part via ``combine_with_window``.
    """
    interpret = resolve_interpret(interpret)
    return paged_chunk_attention_kernel(
        q, k_pages, v_pages, block_tables.astype(jnp.int32),
        ctx_lens.astype(jnp.int32), scale=scale, interpret=interpret)


@partial(jax.jit, static_argnames=("block_size", "scale", "interpret"))
def paged_chunk_attention_full(q, k_pages, v_pages, block_tables, ctx_lens,
                               win_k, win_v, win_pos, win_valid, *,
                               block_size: int, scale=None, interpret=None):
    """Complete chunk-step attention: paged-prefix partial (Pallas) combined
    exactly with the bidirectional in-window part (block-causal), the full
    per-iteration attention of Optimus chunked decoding."""
    from repro.models.layers import block_causal_mask, sdpa_partial

    interpret = resolve_interpret(interpret)
    acc_p, m_p, l_p = paged_chunk_attention_kernel(
        q, k_pages, v_pages, block_tables.astype(jnp.int32),
        ctx_lens.astype(jnp.int32), scale=scale, interpret=interpret)

    B, c, H, D = q.shape
    offs = jnp.arange(c)
    valid = offs[None, :] < win_valid[:, None]
    sm = block_causal_mask(win_pos, win_pos, block_size)
    sm = (sm & valid[:, None, :] & valid[:, :, None]) | \
        jnp.eye(c, dtype=bool)[None]
    acc_w, m_w, l_w = sdpa_partial(q, win_k, win_v, sm[:, None], scale=scale)
    return combine_flash_partials([(acc_p, m_p, l_p), (acc_w, m_w, l_w)],
                                  out_dtype=q.dtype)


@partial(jax.jit, static_argnames=("block_size", "q_tile", "kv_tile",
                                   "scale", "interpret"))
def block_diffusion_attention(q, k, v, lengths, *, block_size: int,
                              q_tile: int = 128, kv_tile: int = 128,
                              scale=None, interpret=None):
    """Block-causal flash attention (prefill / training forward)."""
    interpret = resolve_interpret(interpret)
    return block_diffusion_attention_kernel(
        q, k, v, lengths.astype(jnp.int32), block_size=block_size,
        q_tile=q_tile, kv_tile=kv_tile, scale=scale, interpret=interpret)

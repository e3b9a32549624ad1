"""Pure-jnp oracles for the Pallas kernels."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def paged_chunk_ref(q, k_pages, v_pages, block_tables, ctx_lens, layer=None,
                    *, scale: float | None = None):
    """Oracle for chunked paged attention partials.

    Gathers pages into a contiguous [B, S, KVH, D] cache and computes masked
    flash partials (acc fp32, m, l) with shapes matching the kernel output.
    A stacked [L, P, ps, KVH, D] pool is read at ``layer``, as the kernel
    reads it.
    """
    if layer is not None:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
    B, c, H, D = q.shape
    P, ps, KVH, _ = k_pages.shape
    G = H // KVH
    n_slots = block_tables.shape[1]
    S = n_slots * ps
    scale = scale if scale is not None else 1.0 / np.sqrt(D)

    k = k_pages[block_tables].reshape(B, S, KVH, D)
    v = v_pages[block_tables].reshape(B, S, KVH, D)

    qg = q.reshape(B, c, KVH, G, D)
    s = jnp.einsum("bckgd,bskd->bkgcs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    valid = (jnp.arange(S)[None, :] < ctx_lens[:, None])[:, None, None, None]
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    e = jnp.where(valid, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(e, axis=-1)
    acc = jnp.einsum("bkgcs,bskd->bkgcd", e, v.astype(jnp.float32))
    # match kernel layout [B, c, H, D] / [B, c, H]
    acc = acc.transpose(0, 3, 1, 2, 4).reshape(B, c, H, D)
    m = m.transpose(0, 3, 1, 2).reshape(B, c, H)
    l = l.transpose(0, 3, 1, 2).reshape(B, c, H)
    return acc, m, l


def combine_ref(parts, out_dtype=jnp.float32):
    """Combine flash partials [(acc, m, l), ...] exactly (shared
    implementation: :func:`repro.kernels.ops.combine_flash_partials`;
    imported lazily — ops imports this module at top level)."""
    from repro.kernels.ops import combine_flash_partials
    return combine_flash_partials(parts, out_dtype=out_dtype)


def block_diffusion_ref(q, k, v, lengths, *, block_size: int,
                        scale: float | None = None):
    """Oracle for block-causal flash attention: q/k/v [B,T,H|KVH,D]."""
    B, T, H, D = q.shape
    KVH = k.shape[2]
    G = H // KVH
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    qg = q.reshape(B, T, KVH, G, D)
    s = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(T)
    ok = (pos[None, :] // block_size <= pos[:, None] // block_size)
    ok = ok[None, None, None] & \
        (pos[None, :] < lengths[:, None])[:, None, None, None]
    s = jnp.where(ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(ok, p, 0.0)
    out = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
    return out.reshape(B, T, H, D).astype(q.dtype)

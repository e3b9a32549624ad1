"""Decoder-only LM covering the dense / moe / hybrid / ssm / vlm families.

One parameter layout, four execution entry points:

* ``apply``         — full-sequence forward (training; causal or block-causal).
* ``prefill``       — full-sequence forward that also writes the KV cache /
                      recurrent states (serving admission).
* ``chunk_forward`` — diffusion-window forward: a ``c``-token window per
                      request attends to the frozen prefix cache plus itself
                      (block-causal), returning window logits and window KV.
                      This is the per-iteration unit of Optimus's streaming
                      chunked decoding.  AR decoding is the ``c=1`` special
                      case with causal semantics.
* ``freeze``        — slide the window: write the leading committed window KV
                      entries into the cache and advance ``len``.
* ``advance_states``— advance recurrent states over committed tokens (rwkv AR
                      step; hybrid block-commit, which also rewrites the
                      block's attention KV).

Layers are stacked along a leading axis and executed with ``lax.scan`` so HLO
size is depth-independent (512-device compiles stay fast).  Hybrid (Jamba)
models scan over *periods* of ``attn_period`` heterogeneous layers.

Diffusion-window semantics per family (DESIGN.md §6):
  dense/moe/vlm — window slides token-by-token past committed prefix (paper's
      streaming chunked decoding, prefix KV frozen via ``freeze``).
  hybrid — window is pinned at the current block start (recurrent layers
      recompute the ≤block_size window from the block-start state each step);
      ``advance_states`` commits a finished block.
  ssm (rwkv6) — diffusion decoding inapplicable; native AR decode via
      ``advance_states`` with T=1.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.kernels import ref as kernel_ref
from repro.kernels.chunked_paged_attn import paged_chunk_attention_kernel
from repro.kernels.ops import resolve_interpret
from repro.models import ssm
from repro.models.common import (ArchConfig, KeyGen, dense_init_a,
                                 embed_init_a)
from repro.models.layers import (attn_output, axes_attention, axes_mlp,
                                 axes_norm, block_causal_mask, causal_mask,
                                 combine_partials, flash_partial,
                                 flash_partial_aligned, init_attention,
                                 init_mlp, init_norm, mlp_block, qkv_project,
                                 rms_norm, sdpa_partial)
from repro.models.moe import axes_moe, init_moe, moe_block


def _stack_init(init_fn, kg, cfg, n, abstract):
    """Initialize ``n`` stacked copies of a param subtree (leading dim n).

    The copies come out of one ``lax.scan`` that threads the key stream
    layer by layer (the same draws as ``n`` sequential inits), so under
    ``jax.jit(model.init)`` the stack is written in place — no list of
    per-layer copies beside it — and the body compiles once, not ``n``
    times."""
    if abstract:
        one = init_fn(kg, cfg, abstract=True)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n,) + tuple(s.shape), s.dtype), one)

    def body(key, _):
        layer_kg = KeyGen(key)
        sub = init_fn(layer_kg, cfg, abstract=False)
        return layer_kg.key, sub

    kg.key, stacked = jax.lax.scan(body, kg.key, None, length=n)
    return stacked


def _stack_axes(axes_fn, cfg):
    return jax.tree.map(lambda t: ("layers",) + t, axes_fn(cfg),
                        is_leaf=lambda x: isinstance(x, tuple))


def _scatter_kv(cache_kv, new_kv, idx):
    """cache [L,B,S,KVH,hd] ← new [L,B,T,KVH,hd] at idx [B,T] (OOB drops).

    Implemented as a one-hot contraction + select rather than a scatter:
    scattering along the sequence dim (sharded over the model axis for
    split-KV decode) triggers XLA SPMD's involuntary full rematerialization
    — replicating the multi-GB cache per step — whereas the one-hot einsum
    partitions cleanly (T ≤ chunk_size ≤ 32, so the one-hot is tiny and the
    extra FLOPs are negligible).
    """
    S = cache_kv.shape[2]
    oh = (idx[:, :, None] == jnp.arange(S)[None, None, :])     # [B,T,S]
    upd = jnp.einsum("bts,lbtkd->lbskd", oh.astype(cache_kv.dtype),
                     new_kv.astype(cache_kv.dtype))
    written = jnp.any(oh, axis=1)                              # [B,S]
    return jnp.where(written[None, :, :, None, None], upd, cache_kv)


def _page_dest(block_tables, positions, keep, page_size: int, n_pages: int):
    """Flat page-pool destinations [B,T] for absolute token positions.

    ``keep`` masks live entries; everything else maps to the out-of-bounds
    sentinel ``n_pages * page_size`` so the scatter drops it.  Page indices
    are clipped into the table so padded rows (table width > pages owned)
    never index out of bounds — their ``keep`` is False anyway.
    """
    W = block_tables.shape[1]
    pidx = jnp.clip(positions // page_size, 0, W - 1)
    page = jnp.take_along_axis(block_tables, pidx, axis=1)
    return jnp.where(keep, page * page_size + positions % page_size,
                     n_pages * page_size)


def _scatter_pages(pages, new, dest):
    """pages [L,P,ps,KVH,hd] ← new [L,B,T,KVH,hd] at flat dest [B,T].

    Token-granular scatter into the paged pool.  Distinct live destinations
    never collide (each (page, offset) is owned by one request position);
    dropped entries all share the OOB sentinel.
    """
    L, P, ps, KVH, hd = pages.shape
    flat = pages.reshape(L, P * ps, KVH, hd)
    flat = flat.at[:, dest.reshape(-1)].set(
        new.astype(pages.dtype).reshape(L, -1, KVH, hd), mode="drop")
    return flat.reshape(L, P, ps, KVH, hd)


def _scatter_paged(pages, new, dest, kv_shard=None):
    """Page-pool scatter, dispatching to the shard-local variant when the
    pool is sharded (``kv_shard``: a ``KVShardSpec``) — each shard drops
    out-of-shard destinations so no KV crosses the kv axis and XLA keeps
    aliasing the per-shard pool buffers (donation).

    COW safety: the scatter itself never needs to know about sharing —
    the allocator's ``ensure_private`` runs *before* any dispatch that
    writes a page, so by the time destinations reach here every written
    page is refcount-1 and unregistered.  The page-granular movement
    primitives live below (:func:`copy_pages`, :func:`write_pages`)."""
    if kv_shard is None:
        return _scatter_pages(pages, new, dest)
    from repro.distributed.collectives import scatter_pages_sharded
    return scatter_pages_sharded(pages, new, dest, kv_shard)


def copy_pages(cache, src, dst):
    """Whole-page device-side copy: ``cache[name][:, dst[i]] ←
    cache[name][:, src[i]]`` for every pool array in ``cache``.

    This is the copy-on-write kernel: callers jit it with
    ``donate_argnums=(0,)`` so the pool buffers alias in place (same
    donation contract as the decode scatter).  All gathers read the
    *input* array before any scatter lands, so chained src/dst overlaps
    within one batched call are safe; duplicate (src, dst) pairs (the
    pow-2 index padding) are idempotent."""
    return {name: arr.at[:, dst].set(arr[:, src])
            for name, arr in cache.items()}


def write_pages(cache, dst, k_new, v_new):
    """Whole-page host→device swap-in scatter: page ``dst[i]`` of the pool
    receives ``k_new[:, i]`` / ``v_new[:, i]`` ([L, n, ps, KVH, hd]).
    Jitted with ``donate_argnums=(0,)`` by the allocator so the pool
    aliases in place; duplicate padded indices write identical data."""
    k, v = cache["k_pages"], cache["v_pages"]
    return {"k_pages": k.at[:, dst].set(k_new.astype(k.dtype)),
            "v_pages": v.at[:, dst].set(v_new.astype(v.dtype))}


class TransformerLM:
    """Family-dispatching decoder-only LM."""

    def __init__(self, cfg: ArchConfig):
        assert cfg.family in ("dense", "moe", "hybrid", "ssm", "vlm"), cfg.family
        self.cfg = cfg
        if cfg.family == "hybrid":
            assert cfg.attn_period > 0 and cfg.n_layers % cfg.attn_period == 0
            self.n_periods = cfg.n_layers // cfg.attn_period
        else:
            self.n_periods = cfg.n_layers

    # ------------------------------------------------------------------
    # Layer-position structure
    # ------------------------------------------------------------------
    def _positions(self):
        """(mixer, ffn) kinds for each position inside one scan step."""
        cfg = self.cfg
        if cfg.family == "hybrid":
            out = []
            for j in range(cfg.attn_period):
                mixer = "attn" if cfg.is_attn_layer(j) else "mamba"
                ffn = "moe" if cfg.is_moe_layer(j) else "mlp"
                out.append((mixer, ffn))
            return out
        if cfg.family == "ssm":
            return [("rwkv_tm", "rwkv_cm")]
        mixer = "attn"
        ffn = "moe" if cfg.n_experts > 0 else "mlp"
        return [(mixer, ffn)]

    def attn_positions(self):
        return [j for j, (m, _) in enumerate(self._positions()) if m == "attn"]

    @property
    def has_kv(self):
        return bool(self.attn_positions())

    _MIXER_INIT = {
        "attn": (init_attention, axes_attention),
        "mamba": (ssm.init_mamba, ssm.axes_mamba),
        "rwkv_tm": (ssm.init_rwkv_timemix, ssm.axes_rwkv_timemix),
    }
    _FFN_INIT = {
        "mlp": (init_mlp, axes_mlp),
        "moe": (init_moe, axes_moe),
        "rwkv_cm": (ssm.init_rwkv_chanmix, ssm.axes_rwkv_chanmix),
    }

    # ------------------------------------------------------------------
    # Init
    # ------------------------------------------------------------------
    def init(self, rng, abstract: bool = False):
        cfg = self.cfg
        kg = KeyGen(rng)
        n = self.n_periods
        blocks = {}
        for j, (mixer, ffn) in enumerate(self._positions()):
            mi, _ = self._MIXER_INIT[mixer]
            fi, _ = self._FFN_INIT[ffn]
            blocks[f"pos{j}"] = {
                "norm1": _stack_init(init_norm, kg, cfg, n, abstract),
                "mixer": _stack_init(mi, kg, cfg, n, abstract),
                "norm2": _stack_init(init_norm, kg, cfg, n, abstract),
                "ffn": _stack_init(fi, kg, cfg, n, abstract),
            }
        params = {
            "embed": embed_init_a(kg(), (cfg.vocab_size, cfg.d_model), cfg.pdt,
                                  abstract=abstract),
            "blocks": blocks,
            "final_norm": init_norm(kg, cfg, abstract=abstract),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init_a(kg(), (cfg.d_model, cfg.vocab_size),
                                             cfg.pdt, abstract=abstract)
        return params

    def logical_axes(self):
        cfg = self.cfg
        blocks = {}
        for j, (mixer, ffn) in enumerate(self._positions()):
            _, ma = self._MIXER_INIT[mixer]
            _, fa = self._FFN_INIT[ffn]
            blocks[f"pos{j}"] = {
                "norm1": _stack_axes(axes_norm, cfg),
                "mixer": _stack_axes(ma, cfg),
                "norm2": _stack_axes(axes_norm, cfg),
                "ffn": _stack_axes(fa, cfg),
            }
        axes = {
            "embed": ("vocab_p", "embed_p"),
            "blocks": blocks,
            "final_norm": axes_norm(cfg),
        }
        if not cfg.tie_embeddings:
            axes["lm_head"] = ("embed_p", "vocab_p")
        return axes

    # ------------------------------------------------------------------
    # Embedding / head
    # ------------------------------------------------------------------
    def embed(self, params, tokens, mm_embeds=None, mm_mask=None):
        cfg = self.cfg
        x = params["embed"].astype(cfg.cdt)[tokens]
        if mm_embeds is not None:
            x = jnp.where(mm_mask[..., None], mm_embeds.astype(cfg.cdt), x)
        return shard(x, "batch", "seq", "embed")

    def head(self, params, x):
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = (x @ w.astype(cfg.cdt)).astype(jnp.float32)
        return shard(logits, "batch", "seq", "vocab")

    # ------------------------------------------------------------------
    # Core scanned stack
    # ------------------------------------------------------------------
    def _mixer_apply(self, kind, p, x, positions, shared, lx):
        """Returns (y, kv_or_none, new_state_or_none)."""
        cfg = self.cfg
        if kind == "attn":
            q, k, v = qkv_project(p, cfg, x, positions)
            pos1d = positions if positions.ndim == 2 else positions[:, 0, :]
            parts = []
            if "cache_k" in lx:
                kc = lx["cache_k"].astype(cfg.cdt)
                vc = lx["cache_v"].astype(cfg.cdt)
                B, S = kc.shape[0], kc.shape[1]
                k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
                parts.append(flash_partial(
                    q, kc, vc, q_pos=pos1d, k_pos=k_pos,
                    k_valid=k_pos < shared["cache_len"][:, None], kind="all"))
            if "pool_layer" in lx:
                # paged prefix: block-table-indirected flash partial over the
                # page pool (Pallas chunked-paged-attention kernel, or the
                # pure-jnp oracle when paged_attn_impl == "ref").  The pool
                # is the whole stacked [L, P, ps, KVH, hd] array, read in
                # place at this layer's index: neither sliced per layer nor
                # converted (the kernel casts each page it fetches).  With a
                # sharded pool the partial is computed split-KV over the kv
                # mesh axis — each shard attends over its local pages only
                # and the partials merge exactly (pmax/psum) on device.
                args = (q, shared["k_pages"], shared["v_pages"],
                        shared["block_tables"], shared["ctx_lens"])
                ks = shared.get("kv_shard")
                interpret = resolve_interpret(shared["paged_interpret"])
                if ks is not None:
                    from repro.distributed.collectives import \
                        split_kv_paged_partial
                    parts.append(split_kv_paged_partial(
                        *args, shared["shard_offs"], ks, lx["pool_layer"],
                        impl=shared["paged_impl"], interpret=interpret))
                elif shared["paged_impl"] == "ref":
                    parts.append(kernel_ref.paged_chunk_ref(
                        *args, lx["pool_layer"]))
                else:
                    parts.append(paged_chunk_attention_kernel(
                        *args, lx["pool_layer"], interpret=interpret))
            if "self_flash" in shared:
                sf = shared["self_flash"]
                B, T = pos1d.shape
                if sf.get("aligned") and sf["kind"] in ("causal",
                                                        "block_causal"):
                    # triangular flash: statically skips fully-masked
                    # above-diagonal chunk pairs (≈2× attention FLOPs)
                    parts.append(flash_partial_aligned(
                        q, k, v, lengths=sf["lengths"], kind=sf["kind"],
                        block_size=cfg.block_size))
                else:
                    parts.append(flash_partial(
                        q, k, v, q_pos=pos1d, k_pos=pos1d,
                        k_valid=jnp.arange(T)[None, :] < sf["lengths"][:, None],
                        kind=sf["kind"], block_size=cfg.block_size))
            else:
                parts.append(sdpa_partial(q, k, v, shared["self_mask"]))
            out = combine_partials(parts, x.dtype)
            return attn_output(p, cfg, out), (k, v), None
        if kind == "mamba":
            y, st = ssm.mamba_seq(p, cfg, x, lx["state"])
            return y, None, st
        if kind == "rwkv_tm":
            y, st = ssm.rwkv_timemix(p, cfg, x, lx["state"])
            return y, None, st
        raise ValueError(kind)

    def _ffn_apply(self, kind, p, x, lx):
        cfg = self.cfg
        if kind == "mlp":
            return mlp_block(p, cfg, x), None
        if kind == "moe":
            return moe_block(p, cfg, x), None
        if kind == "rwkv_cm":
            return ssm.rwkv_chanmix(p, cfg, x, lx["state"])
        raise ValueError(kind)

    def _stack(self, params, x, positions, shared, per_layer_xs):
        """Run the scanned layer stack.

        ``shared``: masks closed over (same for every layer).
        ``per_layer_xs``: pytree whose leaves have leading dim n_periods —
        attention cache slices and recurrent states per position.
        Returns (x, kvs, states): kvs/states keyed by position, leaves with
        leading n_periods dim.
        """
        cfg = self.cfg
        pos_kinds = self._positions()

        def body(x, inp):
            blk, lxs = inp
            kv_out, state_out = {}, {}
            for j, (mixer, ffn) in enumerate(pos_kinds):
                p = blk[f"pos{j}"]
                lx = lxs.get(f"pos{j}", {})
                h = rms_norm(x, p["norm1"]["scale"], cfg.norm_eps)
                y, kv, st = self._mixer_apply(mixer, p["mixer"], h, positions,
                                              shared, lx)
                x = x + y
                h = rms_norm(x, p["norm2"]["scale"], cfg.norm_eps)
                y, fst = self._ffn_apply(ffn, p["ffn"], h,
                                         lxs.get(f"ffn{j}", {}))
                x = x + y
                if kv is not None:
                    kv_out[f"pos{j}"] = kv
                if st is not None:
                    state_out[f"pos{j}"] = st
                if fst is not None:
                    state_out[f"ffn{j}"] = fst
            return x, (kv_out, state_out)

        if cfg.remat:
            body = jax.checkpoint(body)

        x, (kvs, states) = jax.lax.scan(body, x, (params["blocks"],
                                                  per_layer_xs))
        return x, kvs, states

    # ------------------------------------------------------------------
    # Recurrent-state helpers
    # ------------------------------------------------------------------
    def _fresh_states(self, kind, B, dtype):
        cfg = self.cfg
        n = self.n_periods

        def stackit(st):
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n,) + a.shape).astype(a.dtype), st)

        if kind == "mamba":
            return stackit(ssm.mamba_init_state(cfg, B, dtype))
        if kind == "rwkv_tm":
            st = ssm.rwkv_init_state(cfg, B, dtype)
            return stackit({"tm_prev": st["tm_prev"], "wkv": st["wkv"]})
        if kind == "rwkv_cm":
            st = ssm.rwkv_init_state(cfg, B, dtype)
            return stackit({"cm_prev": st["cm_prev"]})
        raise ValueError(kind)

    def _state_xs(self, B, dtype, cache=None):
        """Per-layer recurrent-state xs (fresh, or read from cache)."""
        out = {}
        for j, (mixer, ffn) in enumerate(self._positions()):
            if mixer in ("mamba", "rwkv_tm"):
                out[f"pos{j}"] = {"state":
                                  cache["states"][f"pos{j}"] if cache else
                                  self._fresh_states(mixer, B, dtype)}
            if ffn == "rwkv_cm":
                out[f"ffn{j}"] = {"state":
                                  cache["states"][f"ffn{j}"] if cache else
                                  self._fresh_states("rwkv_cm", B, dtype)}
        return out

    def _cache_xs(self, cache):
        """Per-layer attention-cache xs."""
        out = {}
        if self.has_kv and cache is not None and "k" in cache:
            for j in self.attn_positions():
                out[f"pos{j}"] = {"cache_k": cache["k"], "cache_v": cache["v"]}
        return out

    def _collect_kv(self, kvs):
        """kvs from scan → stacked [L_attn, B, T, KVH, hd] k and v."""
        ks = [kvs[f"pos{j}"][0] for j in self.attn_positions()]
        vs = [kvs[f"pos{j}"][1] for j in self.attn_positions()]
        if not ks:
            return None
        # each is [n_periods, B, T, KVH, hd]; one attn per period in all archs
        return {"k": ks[0], "v": vs[0]} if len(ks) == 1 else \
            {"k": jnp.concatenate(ks, 0), "v": jnp.concatenate(vs, 0)}

    # ------------------------------------------------------------------
    # Public entry points
    # ------------------------------------------------------------------
    def apply(self, params, tokens, positions=None, mask_mode="causal",
              lengths=None, mm_embeds=None, mm_mask=None):
        """Full forward → logits [B,T,V] (training path)."""
        cfg = self.cfg
        B, T = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        kind = {"causal": "causal", "block_causal": "block_causal",
                "bidirectional": "all"}[mask_mode]
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)
        aligned = positions.ndim == 2 and positions.shape == (B, T)
        shared = {"self_flash": {"kind": kind, "lengths": lengths,
                                 "aligned": True}}
        x = self.embed(params, tokens, mm_embeds, mm_mask)
        per_layer = self._state_xs(B, x.dtype)
        x, _, _ = self._stack(params, x, positions, shared, per_layer)
        return self.head(params, x)

    # -- serving ---------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        cache: dict[str, Any] = {"len": jnp.zeros((batch,), jnp.int32)}
        n_attn_stack = len(self.attn_positions()) * self.n_periods
        if n_attn_stack:
            shp = (self.n_periods * len(self.attn_positions()), batch,
                   max_len, cfg.n_kv_heads, cfg.hd)
            cache["k"] = jnp.zeros(shp, dtype)
            cache["v"] = jnp.zeros(shp, dtype)
        states = self._state_xs(batch, dtype)
        if states:
            cache["states"] = {k: v["state"] for k, v in states.items()}
        return cache

    def cache_logical_axes(self, cache):
        """Logical axes for the cache pytree (kv_seq enables split-KV)."""
        def one(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if name in ("k", "v"):
                return ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
            if name in ("k_pages", "v_pages"):   # page dim sharded for
                return ("layers", "kv_pages", None,  # split-KV paged decode
                        "kv_heads", "head_dim")      # (kv_shard_rules)
            if name == "len":
                return ("batch",)
            if name == "wkv":
                return ("layers", "batch", "heads", None, None)
            if name == "conv":                 # [L, B, d_conv-1, d_inner]
                return ("layers", "batch", None, "mlp")
            if name == "ssm":                  # [L, B, d_inner, d_state]
                return ("layers", "batch", "mlp", None)
            return ("layers", "batch") + (None,) * (leaf.ndim - 2)
        return jax.tree_util.tree_map_with_path(one, cache)

    def prefill(self, params, tokens, lengths, cache, positions=None,
                mask_mode=None, mm_embeds=None, mm_mask=None,
                head_mode="all"):
        """Forward prompt, writing KV/state cache. Returns (logits, cache).

        head_mode: "all" → logits for every position (tests); "last" →
        only the last valid position (serving — avoids the T×V logits
        blow-up at 32k prefill); "none" → no logits.
        """
        cfg = self.cfg
        B, T = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        if mask_mode is None:
            mask_mode = "block_causal" if cfg.diffusion else "causal"
        pos1d = positions if positions.ndim == 2 else positions[:, 0, :]
        shared = {"self_flash": {"kind": mask_mode, "lengths": lengths,
                                 "aligned": positions is not None}}
        x = self.embed(params, tokens, mm_embeds, mm_mask)
        per_layer = self._state_xs(B, x.dtype)
        x, kvs, states = self._stack(params, x, positions, shared, per_layer)
        if head_mode == "last":
            idx = jnp.clip(lengths - 1, 0, T - 1)
            xl = jnp.take_along_axis(
                x, idx[:, None, None].astype(jnp.int32), axis=1)
            logits = self.head(params, xl)
        elif head_mode == "none":
            logits = None
        else:
            logits = self.head(params, x)

        new_cache = dict(cache)
        kv = self._collect_kv(kvs)
        if kv is not None and "k" in cache:
            # Admission fills positions [0, T) wholesale: mask + pad instead
            # of scatter (dynamic scatter onto the sharded cache triggers
            # XLA SPMD's involuntary full rematerialization → cache-sized
            # replicated temporaries at 32k prefill).
            S = cache["k"].shape[2]
            keep = (jnp.arange(T)[None, :] < lengths[:, None])

            def place(new, old):
                x = jnp.where(keep[None, :, :, None, None], new, 0)
                x = x.astype(old.dtype)
                if S > T:
                    x = jnp.pad(x, ((0, 0), (0, 0), (0, S - T), (0, 0),
                                    (0, 0)))
                return shard(x, "layers", "batch", "kv_seq", "kv_heads",
                             "head_dim")

            new_cache["k"] = place(kv["k"], cache["k"])
            new_cache["v"] = place(kv["v"], cache["v"])
        if states:
            new_cache["states"] = states
        new_cache["len"] = lengths.astype(jnp.int32)
        return logits, new_cache

    def _window_masks(self, cache, positions, valid, c):
        cfg = self.cfg
        if cfg.diffusion:
            sm = block_causal_mask(positions, positions, cfg.block_size)
        else:
            sm = causal_mask(positions, positions)
        sm = sm & valid[:, None, :] & valid[:, :, None]
        sm = sm | jnp.eye(c, dtype=bool)[None]
        shared = {"self_mask": sm[:, None]}
        if self.has_kv and "k" in cache:
            shared["cache_len"] = cache["len"]
        return shared

    def chunk_forward(self, params, cache, win_tokens, win_start, win_valid,
                      mm_embeds=None, mm_mask=None):
        """Diffusion-window forward.

        win_tokens [B,c] (mask token at uncommitted positions),
        win_start [B] (== cache['len'] for sliding-window families),
        win_valid [B] (#valid window slots, for in-block clamping).
        Returns (logits [B,c,V], win_kv {"k": [L_attn,B,c,KVH,hd], ...}).
        """
        B, c = win_tokens.shape
        offs = jnp.arange(c, dtype=jnp.int32)
        positions = win_start[:, None] + offs[None, :]
        valid = offs[None, :] < win_valid[:, None]
        shared = self._window_masks(cache, positions, valid, c)
        per_layer = {**self._cache_xs(cache),
                     **self._state_xs(B, self.cfg.cdt, cache=cache
                                      if "states" in cache else None)}
        x = self.embed(params, win_tokens, mm_embeds, mm_mask)
        x, kvs, _ = self._stack(params, x, positions, shared, per_layer)
        logits = self.head(params, x)
        return logits, self._collect_kv(kvs)

    def freeze(self, cache, win_kv, win_start, n_adv):
        """Write the first n_adv[b] window KV entries into the cache and
        advance ``len``.  Sliding-window (attention-only) families only."""
        new_cache = dict(cache)
        if win_kv is not None and "k" in cache:
            c = win_kv["k"].shape[2]
            S = cache["k"].shape[2]
            offs = jnp.arange(c, dtype=jnp.int32)
            keep = offs[None, :] < n_adv[:, None]
            idx = jnp.where(keep, win_start[:, None] + offs[None, :], S)
            new_cache["k"] = _scatter_kv(cache["k"], win_kv["k"], idx)
            new_cache["v"] = _scatter_kv(cache["v"], win_kv["v"], idx)
        new_cache["len"] = cache["len"] + n_adv.astype(jnp.int32)
        return new_cache

    # -- paged serving ---------------------------------------------------
    #
    # The paged cache variant replaces the dense per-slot [L,B,S,KVH,hd]
    # arrays with a block-table-indirected page pool [L,P,ps,KVH,hd] shared
    # by every in-flight request (NanoFlow-style: capacity is bounded by
    # pages, not slots).  Supported for attention-only families
    # (dense/moe/vlm); recurrent families keep the dense-slot path.

    PAGED_FAMILIES = ("dense", "moe", "vlm")

    def supports_paged(self) -> bool:
        return self.cfg.family in self.PAGED_FAMILIES and self.has_kv

    def _check_paged(self):
        if not self.supports_paged():
            raise ValueError(
                f"paged KV serving needs an attention-only family "
                f"(got {self.cfg.family!r})")

    def paged_kv_dims(self) -> tuple[int, int, int]:
        """(n_kv_layers, n_kv_heads, head_dim) — the model-derived half of
        the page-pool shape.  Single source for both
        :meth:`init_paged_cache` and ``PagedKVAllocator.init_storage``."""
        return (self.n_periods * len(self.attn_positions()),
                self.cfg.n_kv_heads, self.cfg.hd)

    def init_paged_cache(self, n_pages: int, page_size: int | None = None,
                         dtype=jnp.float32):
        """Page-pool cache: {'k_pages','v_pages'} [L,P,ps,KVH,hd] (the same
        arrays ``PagedKVAllocator.init_storage`` owns in serving)."""
        self._check_paged()
        ps = page_size if page_size is not None else self.cfg.kv_page_size
        L, KVH, hd = self.paged_kv_dims()
        shp = (L, n_pages, ps, KVH, hd)
        return {"k_pages": jnp.zeros(shp, dtype),
                "v_pages": jnp.zeros(shp, dtype)}

    def prefill_paged(self, params, cache, tokens, lengths, block_tables,
                      mm_embeds=None, mm_mask=None, head_mode="logits",
                      kv_shard=None):
        """Batched prompt forward writing KV into the page pool.

        tokens [B,T] (row-padded), lengths [B], block_tables [B,W] int32.
        The whole admission wave runs as ONE forward, unlike the dense
        path's sequential per-slot prefill.

        head_mode (static): "logits" returns the last-valid-position logits
        [B,V]; "sample" reduces them on device via
        :func:`repro.kernels.ops.softmax_confidence_device` and returns
        (conf [B], tok [B]) — only AR requests ever read the prefill head,
        and they need just the argmax, so serving never ships [B,V] logits
        to the host.  Returns (head output, new page cache).

        ``kv_shard`` (static ``KVShardSpec`` or None): sharded page pool —
        the KV scatter stays shard-local (block tables carry GLOBAL page
        ids; each shard drops pages it doesn't own).
        """
        self._check_paged()
        cfg = self.cfg
        B, T = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        mask_mode = "block_causal" if cfg.diffusion else "causal"
        shared = {"self_flash": {"kind": mask_mode, "lengths": lengths,
                                 "aligned": True}}
        x = self.embed(params, tokens, mm_embeds, mm_mask)
        x, kvs, _ = self._stack(params, x, positions, shared, {})
        idx = jnp.clip(lengths - 1, 0, T - 1)
        xl = jnp.take_along_axis(
            x, idx[:, None, None].astype(jnp.int32), axis=1)
        logits = self.head(params, xl)[:, 0]
        kv = self._collect_kv(kvs)
        P, ps = cache["k_pages"].shape[1], cache["k_pages"].shape[2]
        keep = positions < lengths[:, None]
        dest = _page_dest(block_tables, positions, keep, ps, P)
        new_cache = {
            "k_pages": _scatter_paged(cache["k_pages"], kv["k"], dest,
                                      kv_shard),
            "v_pages": _scatter_paged(cache["v_pages"], kv["v"], dest,
                                      kv_shard)}
        if head_mode == "sample":
            from repro.kernels.ops import softmax_confidence_device
            conf, tok = softmax_confidence_device(logits)
            return (conf, tok), new_cache
        return logits, new_cache

    def prefill_chunk_paged(self, params, cache, tokens, offsets, valid,
                            block_tables, *, impl: str = "kernel",
                            interpret=None, mm_embeds=None, mm_mask=None,
                            kv_shard=None, shard_offs=None):
        """One resumable prefill chunk per row: forward prompt tokens
        [offsets, offsets + valid) against the pages already written by
        earlier chunks, and scatter this chunk's KV into the pool.

        tokens [B,T] (row-padded chunk tokens), offsets [B] absolute chunk
        start, valid [B] live tokens per row (0 ⇒ padded row, no writes).
        The already-prefilled prefix is read through ``block_tables`` with
        ``ctx_lens = offsets`` — the same paged-prefix partial the decode
        windows use — and the in-window part applies the prefill mask
        (block-causal for diffusion, causal otherwise) over absolute
        positions.  Diffusion chunk boundaries must be block-aligned (a
        mid-block split would hide the block's unprefilled tail from its
        head, diverging from the wave forward); the serving-side
        :class:`~repro.serving.backends.PrefillScheduler` guarantees this.

        Returns (conf [B], tok [B], new page cache): the last-valid-position
        head reduced on device — meaningful only for rows whose prompt
        completes with this chunk (the AR first token), never [B,V] logits.
        """
        from repro.kernels.ops import softmax_confidence_device
        self._check_paged()
        T = tokens.shape[1]
        offs = jnp.arange(T, dtype=jnp.int32)
        positions = offsets[:, None] + offs[None, :]
        validm = offs[None, :] < valid[:, None]
        shared = self._window_masks(cache, positions, validm, T)
        per_layer = self._paged_prefix(shared, cache, block_tables, offsets,
                                       impl, interpret, kv_shard, shard_offs)
        x = self.embed(params, tokens, mm_embeds, mm_mask)
        x, kvs, _ = self._stack(params, x, positions, shared, per_layer)
        idx = jnp.clip(valid - 1, 0, T - 1)
        xl = jnp.take_along_axis(
            x, idx[:, None, None].astype(jnp.int32), axis=1)
        logits = self.head(params, xl)[:, 0]
        kv = self._collect_kv(kvs)
        P, ps = cache["k_pages"].shape[1], cache["k_pages"].shape[2]
        dest = _page_dest(block_tables, positions, validm, ps, P)
        new_cache = {
            "k_pages": _scatter_paged(cache["k_pages"], kv["k"], dest,
                                      kv_shard),
            "v_pages": _scatter_paged(cache["v_pages"], kv["v"], dest,
                                      kv_shard)}
        conf, tok = softmax_confidence_device(logits)
        return conf, tok, new_cache

    def _paged_prefix(self, shared, cache, block_tables, ctx_lens, impl,
                      interpret, kv_shard, shard_offs):
        """Install the paged-prefix fields read by ``_mixer_apply``'s paged
        branch and return the per-layer xs that carry each layer's index.

        The whole stacked pool goes in ``shared``: the scan closes over it
        (a loop-invariant operand, never sliced), and each layer hands the
        kernel its index into the pool's layer dim."""
        attn = self.attn_positions()
        # the pool stacks attention layers position-major (_collect_kv):
        # with one attention position per period its layer is the scan index
        assert len(attn) == 1, "paged pool needs one attention layer/period"
        shared.update(k_pages=cache["k_pages"], v_pages=cache["v_pages"],
                      block_tables=block_tables.astype(jnp.int32),
                      ctx_lens=ctx_lens.astype(jnp.int32),
                      paged_impl=impl, paged_interpret=interpret)
        if kv_shard is not None:
            if shard_offs is None:
                shard_offs = jnp.zeros(ctx_lens.shape, jnp.int32)
            shared.update(kv_shard=kv_shard,
                          shard_offs=shard_offs.astype(jnp.int32))
        return {f"pos{attn[0]}": {
            "pool_layer": jnp.arange(self.n_periods, dtype=jnp.int32)}}

    def chunk_forward_paged(self, params, cache, win_tokens, win_start,
                            win_valid, block_tables, ctx_lens, *,
                            impl: str = "kernel", interpret=None,
                            mm_embeds=None, mm_mask=None,
                            kv_shard=None, shard_offs=None):
        """Diffusion-window forward against the paged prefix cache.

        Same contract as :meth:`chunk_forward`, but the frozen prefix is
        read through block tables: ``impl='kernel'`` runs the Pallas
        chunked-paged-attention kernel (interpret mode off-TPU),
        ``impl='ref'`` the pure-jnp oracle.  ctx_lens [B] is the committed
        prefix length per row (0 for padded rows — their paged partial is
        empty and the in-window diagonal keeps logits finite).
        """
        self._check_paged()
        c = win_tokens.shape[1]
        offs = jnp.arange(c, dtype=jnp.int32)
        positions = win_start[:, None] + offs[None, :]
        valid = offs[None, :] < win_valid[:, None]
        shared = self._window_masks(cache, positions, valid, c)
        per_layer = self._paged_prefix(shared, cache, block_tables, ctx_lens,
                                       impl, interpret, kv_shard, shard_offs)
        x = self.embed(params, win_tokens, mm_embeds, mm_mask)
        x, kvs, _ = self._stack(params, x, positions, shared, per_layer)
        logits = self.head(params, x)
        return logits, self._collect_kv(kvs)

    def freeze_paged(self, cache, win_kv, block_tables, win_start, n_adv,
                     kv_shard=None):
        """Write the first n_adv[b] window KV entries into the page pool
        (the paged counterpart of :meth:`freeze`; 'len' lives with the
        caller's decode state, not in the cache)."""
        c = win_kv["k"].shape[2]
        P, ps = cache["k_pages"].shape[1], cache["k_pages"].shape[2]
        offs = jnp.arange(c, dtype=jnp.int32)
        pos = win_start[:, None] + offs[None, :]
        keep = offs[None, :] < n_adv[:, None]
        dest = _page_dest(block_tables, pos, keep, ps, P)
        return {"k_pages": _scatter_paged(cache["k_pages"], win_kv["k"],
                                          dest, kv_shard),
                "v_pages": _scatter_paged(cache["v_pages"], win_kv["v"],
                                          dest, kv_shard)}

    def decode_step_paged(self, params, cache, win_tokens, win_start,
                          win_valid, block_tables, ctx_lens, n_adv, *,
                          impl: str = "kernel", interpret=None,
                          mm_embeds=None, mm_mask=None,
                          kv_shard=None, shard_offs=None):
        """One fused paged decode iteration: chunk-forward + freeze +
        on-device sampling in a single dispatch.

        Composes :meth:`chunk_forward_paged`, :meth:`freeze_paged` and the
        device softmax-confidence/argmax reduction
        (:func:`repro.kernels.ops.softmax_confidence_device`) so one jitted
        call per step replaces the chunk + freeze pair, and only
        ``2·B·c`` scalars (confidence fp32, token int32) return to the
        host instead of the full ``[B, c, V]`` logits.

        ``n_adv`` [B] is the number of leading window KV entries to freeze
        — precomputable before the step for slide-mode windows (the leading
        committed-at-input run; see :func:`repro.core.chunked.freeze_run`)
        and always 1 for AR rows.  Jit with ``donate_argnums=(1,)`` so the
        page pool aliases in place instead of being copied every step.
        Returns (conf [B, c], tok [B, c], new page cache).
        """
        from repro.kernels.ops import softmax_confidence_device
        logits, win_kv = self.chunk_forward_paged(
            params, cache, win_tokens, win_start, win_valid, block_tables,
            ctx_lens, impl=impl, interpret=interpret,
            mm_embeds=mm_embeds, mm_mask=mm_mask,
            kv_shard=kv_shard, shard_offs=shard_offs)
        new_cache = self.freeze_paged(cache, win_kv, block_tables,
                                      win_start, n_adv, kv_shard=kv_shard)
        conf, tok = softmax_confidence_device(logits)
        return conf, tok, new_cache

    def advance_states(self, params, cache, tokens, lengths,
                       mm_embeds=None, mm_mask=None):
        """Advance recurrent states (and attention KV) over committed
        ``tokens`` [B,T] starting at cache['len'].  Returns (logits, cache)."""
        B, T = tokens.shape
        start = cache["len"]
        offs = jnp.arange(T, dtype=jnp.int32)
        positions = start[:, None] + offs[None, :]
        valid = offs[None, :] < lengths[:, None]
        shared = self._window_masks(cache, positions, valid, T)
        per_layer = {**self._cache_xs(cache),
                     **self._state_xs(B, self.cfg.cdt, cache=cache
                                      if "states" in cache else None)}
        x = self.embed(params, tokens, mm_embeds, mm_mask)
        x, kvs, states = self._stack(params, x, positions, shared, per_layer)
        logits = self.head(params, x)

        new_cache = dict(cache)
        kv = self._collect_kv(kvs)
        if kv is not None and "k" in cache:
            S = cache["k"].shape[2]
            idx = jnp.where(valid, positions, S)
            new_cache["k"] = _scatter_kv(cache["k"], kv["k"], idx)
            new_cache["v"] = _scatter_kv(cache["v"], kv["v"], idx)
        if states:
            new_cache["states"] = states
        new_cache["len"] = cache["len"] + lengths.astype(jnp.int32)
        return logits, new_cache

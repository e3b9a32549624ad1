"""Model module `dense`: a decoder-only transformer whose every layer is
grouped-query attention followed by a SwiGLU MLP (Qwen3-style, without
q/k-norm), with an untied or tied LM head.

A configuration file names its model module by ``"model": "<name>"``
(default ``dense``), and the harness loads ``bench/models/<name>.py`` by
that name.  A model module provides:

* ``SPEC_KEYS``: configuration keys its spec carries beyond the ones
  every configuration has (``bench/spec.py``);
* ``arch_kwargs(spec)``: the program's ``ArchConfig`` keyword arguments;
* the reference's model-specific parts, in float32 with no kernel, page
  pool or batching logic, importing nothing of the program:
  ``init_weights(spec, seed)``, ``prompt_kv(spec, w, tokens, length,
  quant=None)`` and ``window_step(spec, w, ck, cv, win, start, valid,
  tok_a, tok_b, quant=None)``, which :func:`bench.reference.replay` drives
  (a model that differs from this one only in its feed-forward layer binds
  its own ``ffn_weights`` and ``ffn`` into these three);
* the model FLOPs of one dispatch: ``decode_flops(spec, ctx, valid)`` and
  ``prefill_flops(spec, plan)``.

The weights come from the seed by the same draws as the repository's
random initialisation (every matrix N(0, 1/fan_in), the embedding
N(0, 0.02²), norms 1, from one threefry key chain, in the order below),
with the configuration's logit gain as the final norm's scale.
"""

from __future__ import annotations

import numpy as np

from bench import reference as R
from bench import spec as S

SPEC_KEYS = ()


def arch_kwargs(spec: dict) -> dict:
    """``ArchConfig`` keyword arguments for ``spec``."""
    kw = {field: spec[k] for k, field in S.HF_KEYS.items()}
    kw.update({k: spec[k] for k in S.SERVING_KEYS})
    kw.update(name="bench", family="dense", act=spec["hidden_act"],
              param_dtype=spec["dtype"], compute_dtype=spec["dtype"],
              paged_attn_impl="kernel", remat=False)
    return kw


# -- the reference ----------------------------------------------------------

def ffn_weights(spec: dict, key, dt):
    """One layer's SwiGLU weights, drawn in the program's order:
    (key, {"up", "down", "gate"})."""
    d, F = spec["hidden_size"], spec["intermediate_size"]
    out = []
    for shape, fan in (((d, F), d), ((F, d), F), ((d, F), d)):
        key, sub = R.split(key)
        out.append(R.dense_init(sub, shape, dt, fan))
    return key, dict(zip(("up", "down", "gate"), out))


def ffn(spec, lw, x, quant):
    """One layer's SwiGLU MLP of its RMS-normed input ``x``."""
    jax, _ = R.jax_modules()
    h = R.rms(x, spec["rms_norm_eps"])
    g = jax.nn.silu(R.dot(h, R.mat(lw["gate"], quant)))
    return R.dot(g * R.dot(h, R.mat(lw["up"], quant)),
                 R.mat(lw["down"], quant))


def init_weights(spec: dict, seed: int, ffn_weights=ffn_weights):
    """Weights from ``seed`` (one jit, on the default device)."""
    jax, jnp = R.jax_modules()
    d, H, KVH, D, V, L = (spec["hidden_size"], spec["num_attention_heads"],
                          spec["num_key_value_heads"], spec["head_dim"],
                          spec["vocab_size"], spec["num_hidden_layers"])
    dt = jnp.dtype(spec["dtype"])

    def stacked(key, draw):
        return jax.lax.scan(lambda k, _: draw(k), key, None, length=L)

    def norm(key):
        key, _ = R.split(key)
        return key, ()

    def attn(key):
        out = []
        for shape, fan in (((d, H * D), d), ((d, KVH * D), d),
                           ((d, KVH * D), d), ((H * D, d), H * D)):
            key, sub = R.split(key)
            out.append(R.dense_init(sub, shape, dt, fan))
        return key, dict(zip(("wq", "wk", "wv", "wo"), out))

    def draw(key):
        key, _ = stacked(key, norm)
        key, w = stacked(key, attn)
        key, _ = stacked(key, norm)
        key, f = stacked(key, lambda k: ffn_weights(spec, k, dt))
        key, sub = R.split(key)
        w.update(f, embed=(jax.random.normal(sub, (V, d), jnp.float32)
                           * 0.02).astype(dt))
        key, _ = R.split(key)                     # the final norm's draw
        if not spec["tie_word_embeddings"]:
            key, sub = R.split(key)
            w["head"] = R.dense_init(sub, (d, V), dt, d)
        return w

    return jax.jit(draw)(jax.random.PRNGKey(seed))


def _layers(w):
    """The per-layer (stacked) weights of ``w``."""
    return {k: v for k, v in w.items() if k not in ("embed", "head")}


def prompt_kv(spec, w, tokens, length, quant=None, ffn=ffn):
    """Keys and values of a prompt [T] (padded; ``length`` live tokens)
    under block-causal attention: ([L, T, KVH, D], same)."""
    jax, jnp = R.jax_modules()
    T = tokens.shape[0]
    bs = spec["block_size"]
    pos = jnp.arange(T)
    x = R.embed(w, tokens, quant)
    qc = min(R.QUERY_CHUNK, T)

    def layer(x, lw):
        q, k, v = R.qkv(spec, lw, x, pos, quant)

        def block(i):
            qp = i * qc + jnp.arange(qc)
            qi = jax.lax.dynamic_slice_in_dim(q, i * qc, qc)
            mask = (pos[None, :] // bs <= qp[:, None] // bs) \
                & (pos[None, :] < length)
            return R.attend(qi, k, v, mask)

        o = jax.lax.map(block, jnp.arange(T // qc)).reshape(T, -1)
        x = x + R.dot(o, R.mat(lw["wo"], quant))
        return x + ffn(spec, lw, x, quant), (k, v)

    _, (ks, vs) = jax.lax.scan(layer, x, _layers(w))
    return ks, vs


def window_step(spec, w, ck, cv, win, start, valid, tok_a, tok_b,
                quant=None, ffn=ffn):
    """One window per row against its cached prefix.

    ck/cv [L, n, S, KVH, D] (positions < start are the prefix), win [n, W]
    tokens, start/valid [n].  Returns what
    :func:`bench.reference.read_logits` reads of the window's logits, with
    the window's keys and values written into ck/cv at ``start``."""
    jax, jnp = R.jax_modules()
    n, W = win.shape
    S_ = ck.shape[2]
    bs = spec["block_size"]
    pos = start[:, None] + jnp.arange(W)[None]
    ok = jnp.arange(W)[None] < valid[:, None]
    self_mask = (pos[:, None, :] // bs <= pos[:, :, None] // bs) \
        & ok[:, None, :] & ok[:, :, None] | jnp.eye(W, dtype=bool)[None]
    pre_mask = jnp.broadcast_to(
        (jnp.arange(S_)[None, :] < start[:, None])[:, None, :], (n, W, S_))
    x = R.embed(w, win, quant)

    def layer(x, inp):
        lw, pk, pv = inp
        q, k, v = R.qkv(spec, lw, x, pos, quant)
        o = R.attend(q, jnp.concatenate([pk, k], 1),
                     jnp.concatenate([pv, v], 1),
                     jnp.concatenate([pre_mask, self_mask], 2))
        x = x + R.dot(o, R.mat(lw["wo"], quant))
        return x + ffn(spec, lw, x, quant), (k, v)

    x, (ks, vs) = jax.lax.scan(layer, x, (_layers(w), ck, cv))
    h = R.rms(x, spec["rms_norm_eps"], spec["logit_gain"])
    head = w["embed"].T if spec["tie_word_embeddings"] else w["head"]
    return R.read_logits(R.dot(h, R.mat(head, quant)), start, ck, cv, ks, vs,
                         tok_a, tok_b)


# -- model FLOPs --------------------------------------------------------------

def layer_matmul_params(spec: dict) -> int:
    """Weights one token multiplies by in one layer: q, k, v, o
    projections and the gated MLP's three matrices."""
    d, H, KVH, D, F = (spec["hidden_size"], spec["num_attention_heads"],
                       spec["num_key_value_heads"], spec["head_dim"],
                       spec["intermediate_size"])
    return d * H * D + 2 * d * KVH * D + H * D * d + 3 * d * F


def decode_flops(spec: dict, ctx: np.ndarray, valid: np.ndarray) -> float:
    """Model FLOPs one decode dispatch needs: for each valid window
    position of each row,

        2 · L · P_layer + 4 · L · H · D · (ctx + valid) + 2 · d · V

    (matmuls of every layer; attention over the prefix and over the valid
    window, which is bidirectional inside the block; the LM head, whose
    confidence and argmax the commit rule reads at every valid
    position)."""
    L, d, V = (spec["num_hidden_layers"], spec["hidden_size"],
               spec["vocab_size"])
    H, D = spec["num_attention_heads"], spec["head_dim"]
    ctx = np.asarray(ctx, np.float64)
    valid = np.asarray(valid, np.float64)
    per_tok = 2.0 * L * layer_matmul_params(spec) + 2.0 * d * V
    attn = 4.0 * L * H * D * (valid * (ctx + valid)).sum()
    return float(per_tok * valid.sum() + attn)


def prefill_flops(spec: dict, plan) -> float:
    """Model FLOPs one prefill dispatch needs for ``plan`` = [(offset,
    n)]: per prompt token, 2 · L · P_layer, plus attention to the ``offset``
    tokens already in the pool and to the chunk's keys up to the end of
    its own block (block-causal).  No LM head: a diffusion request never
    reads the prefill head."""
    L, H, D, bs = (spec["num_hidden_layers"], spec["num_attention_heads"],
                   spec["head_dim"], spec["block_size"])
    total = 0.0
    for off, n in plan:
        pos = off + np.arange(n)
        seen = np.minimum((pos // bs + 1) * bs, off + n)   # keys attended
        total += 2.0 * L * layer_matmul_params(spec) * n \
            + 4.0 * L * H * D * float(seen.sum())
    return total

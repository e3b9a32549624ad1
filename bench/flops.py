"""Peaks of each chip, and the operations and bytes the work needs,
computed from shapes.

The peaks are keyed by ``jax.Device.device_kind``; a kind that is not in
the table is an error, never a default.
"""

from __future__ import annotations

import numpy as np

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak for device kind {device_kind!r}; add it "
                         f"to bench/flops.py PEAKS with its source") from None


def dtype_bytes(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dtype]


def paged_attn_call(spec: dict, ctx: np.ndarray, rows: np.ndarray):
    """(ops, bytes) one paged-attention kernel call needs in one layer.

    ``ctx[b]`` is row b's prefix length (tokens in the page pool) and
    ``rows[b]`` its live query tokens (valid window positions, or the
    prompt tokens of a prefill chunk).  For each row with ``ctx > 0``:

    * ops   = 4 · H · D · rows · ctx
      (Q·Kᵀ and P·V, two FLOPs per multiply-add);
    * bytes = 2 · ⌈ctx / ps⌉ · ps · KVH · D · e   (K and V pages, read once)
      + rows · H · D · e                           (queries)
      + rows · H · (D + 2) · 4                     (acc, m, l written fp32)

    with ``e`` the pool's bytes per element and ``ps`` the page size.
    Rows with ``ctx = 0`` need nothing: the kernel skips their pages.
    """
    H, KVH, D = (spec["num_attention_heads"], spec["num_key_value_heads"],
                 spec["head_dim"])
    ps, e = spec["kv_page_size"], dtype_bytes(spec["dtype"])
    ctx = np.asarray(ctx, np.float64)
    rows = np.asarray(rows, np.float64)
    live = ctx > 0
    ops = 4.0 * H * D * (rows * ctx)[live].sum()
    pages = np.ceil(ctx[live] / ps) * ps
    nbytes = (2.0 * pages * KVH * D * e).sum() \
        + (rows[live] * H * (D * e + (D + 2) * 4)).sum()
    return float(ops), float(nbytes)


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

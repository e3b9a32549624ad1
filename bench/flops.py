"""Peaks of each chip, and the operations and bytes of the paged-attention
kernel, computed from shapes.  A model's own FLOPs per dispatch are its
model module's (``bench/models/<name>.py``).

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

"""Percentile, rate and window arithmetic; the prefill shape set; the
operations and bytes the roofline and MFU metrics divide by, against
values worked out by hand."""

import numpy as np
import pytest

from bench import flops as F
from bench import spec as S
from bench import stats
from bench.harness import prefill_shapes, pow2_ceil

SPEC = {"num_hidden_layers": 2, "hidden_size": 8, "intermediate_size": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 4,
        "vocab_size": 10, "kv_page_size": 16, "dtype": "bfloat16",
        "block_size": 4}


def test_percentile_interpolates_between_order_statistics():
    assert stats.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == \
        pytest.approx(9.1)
    assert stats.percentile([5.0], 90) == 5.0


def test_per_token_pools_all_time_over_all_tokens():
    # 1 s over 9 tokens and 3 s over 1 token: 4 s / 10, not the mean 1.56
    assert stats.per_token([(1.0, 9), (3.0, 1)]) == pytest.approx(0.4)
    assert stats.per_token([(2.0, 0)]) == 2.0


def test_window_is_half_open_and_rate_divides_by_its_length():
    assert stats.in_window(10.0, 10.0, 40.0)
    assert not stats.in_window(40.0, 10.0, 40.0)
    assert stats.rate(300, 30.0) == 10.0


def test_paged_attn_call_by_hand():
    # two rows: ctx 20 (2 pages of 16 → 32 tokens read), 3 valid rows;
    # ctx 0 needs nothing
    ops, nbytes = F.paged_attn_call(SPEC, [20, 0], [3, 5])
    assert ops == 4 * 4 * 4 * 3 * 20
    kv = 2 * 32 * 2 * 4 * 2          # K and V pages, bf16
    q = 3 * 4 * 4 * 2                # queries, bf16
    out = 3 * 4 * (4 + 2) * 4        # acc, m, l fp32
    assert nbytes == kv + q + out


def test_decode_and_prefill_flops_by_hand():
    dense = S.model_module({})
    p_layer = 8 * 16 + 2 * 8 * 8 + 16 * 8 + 3 * 8 * 16
    assert dense.layer_matmul_params(SPEC) == p_layer
    # one row: ctx 5, 2 valid positions
    per_tok = 2 * 2 * p_layer + 2 * 8 * 10
    attn = 4 * 2 * 4 * 4 * (2 * (5 + 2))
    assert dense.decode_flops(SPEC, [5], [2]) == per_tok * 2 + attn
    # chunk at offset 4, 3 tokens, block 4: positions 4,5,6 see keys < 7
    attn = 4 * 2 * 4 * 4 * (7 + 7 + 7)
    assert dense.prefill_flops(SPEC, [(4, 3)]) == 2 * 2 * p_layer * 3 + attn


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        F.peak("TPU v9 imaginary")
    assert F.peak("TPU v5 lite")["flops"] == 197e12


def test_prefill_shapes_cover_what_a_plan_can_emit():
    from repro.serving.backends import PrefillScheduler
    from repro.serving.request import Request
    budget, align, max_batch, pmin, pmax = 96, 32, 8, 8, 200
    shapes = set(prefill_shapes(budget, align, max_batch, pmin, pmax))
    rng = np.random.default_rng(0)
    seen = set()
    for trial in range(300):
        ps = PrefillScheduler(None, align, target_bc=budget)
        n = int(rng.integers(1, max_batch + 1))
        for i in range(n):
            L = int(np.clip(rng.lognormal(3.5, 1.0), pmin, pmax))
            ps.add(Request(i, 0.0, L, 4))
        for _ in range(int(rng.integers(0, 4))):      # some ticks in
            for req, off, k in ps.plan(int(rng.integers(0, budget))):
                ps.advance(req.rid, k)
        plan = ps.plan(0)
        if plan:
            seen.add((pow2_ceil(len(plan)), pow2_ceil(max(k for *_, k
                                                          in plan))))
    assert seen and seen <= shapes

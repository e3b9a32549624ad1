"""The reference makes the same weights from the seed as the program,
with code of its own, and its prompt pass agrees with the program's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as R
from bench import spec as S

DENSE = S.model_module({})


def _program(spec, seed):
    from repro.launch.serve import init_model
    from repro.models.common import ArchConfig
    return init_model(ArchConfig(**DENSE.arch_kwargs(spec)), seed)


def test_reference_weights_equal_the_programs():
    base = S.load_json(S.ROOT / "bench/configs/sdar-8b-16l.json")
    # the configuration, and one with a tied head and a GQA group of 2
    for cfg in (base, dict(base, tie_word_embeddings=True,
                           smoke=dict(base["smoke"], num_key_value_heads=4))):
        spec = S.model_spec(cfg, smoke=True)
        _, p = _program(spec, 12345)
        w = DENSE.init_weights(spec, 12345)
        blk = p["blocks"]["pos0"]
        pairs = [(blk["mixer"][k], w[k]) for k in ("wq", "wk", "wv", "wo")]
        pairs += [(blk["ffn"][k], w[m]) for k, m in
                  (("w_up", "up"), ("w_down", "down"), ("w_gate", "gate"))]
        pairs.append((p["embed"], w["embed"]))
        if "lm_head" in p:
            pairs.append((p["lm_head"], w["head"]))
        else:
            assert "head" not in w
        for a, b in pairs:
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


OPEN = np.array([False, True, True, True, False])   # [committed, open x3,
                                                    # beyond valid]


@pytest.mark.parametrize("conf,committed,gap", [
    # by the threshold: both over it commit, the third stays
    ([1.0, 0.95, 0.92, 0.5, 0.99], [0, 1, 1, 0, 0], 0.0),
    # one committed 0.02 under the threshold
    ([1.0, 0.88, 0.95, 0.5, 0.99], [0, 1, 1, 0, 0], 0.02),
    # an open position 0.05 over it left uncommitted
    ([1.0, 0.95, 0.5, 0.95, 0.1], [0, 1, 0, 0, 0], 0.05),
    # the fallback: none passes, the most confident commits
    ([1.0, 0.3, 0.6, 0.5, 0.99], [0, 0, 1, 0, 0], 0.0),
    # the fallback on the second best: half the difference each way
    ([1.0, 0.3, 0.5, 0.6, 0.99], [0, 0, 1, 0, 0], 0.05),
    # every open position committed where none passes
    ([1.0, 0.3, 0.5, 0.6, 0.99], [0, 1, 1, 1, 0], 0.6),
    # nothing committed in an open window, or a closed position committed
    ([1.0, 0.3, 0.5, 0.6, 0.99], [0, 0, 0, 0, 0], R.BROKEN),
    ([1.0, 0.3, 0.5, 0.95, 0.99], [1, 0, 0, 1, 0], R.BROKEN),
])
def test_commit_gap_by_hand(conf, committed, gap):
    conf = np.asarray(conf)
    committed = np.asarray(committed, bool)
    assert R.commit_gap(conf, OPEN, committed, 0.9) == pytest.approx(gap)
    if gap == 0.0:
        assert (R.commit_rule(conf, OPEN, 0.9) == committed).all()


def test_prompt_keys_match_the_programs_prefill():
    spec = S.model_spec(S.load_json(
        S.ROOT / "bench/configs/sdar-8b-16l.json"), smoke=True)
    model, p = _program(spec, 5)
    T, P = 64, 50
    toks = np.random.default_rng(0).integers(4, spec["vocab_size"], T)
    L, KVH, D = model.paged_kv_dims()
    cache = model.init_paged_cache(8, 16, dtype=jnp.float32)
    tables = jnp.arange(8, dtype=jnp.int32)[None, :4]
    _, pages = model.prefill_paged(p, cache, jnp.asarray(toks[None]),
                                   jnp.asarray([P]), tables)
    got = np.asarray(pages["k_pages"]).reshape(L, -1, KVH, D)[:, :P]
    with jax.default_matmul_precision("highest"):
        k, _ = DENSE.prompt_kv(spec, DENSE.init_weights(spec, 5),
                               jnp.asarray(toks), P)
    np.testing.assert_allclose(got, np.asarray(k)[:, :P], rtol=1e-4,
                               atol=1e-4)

"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so ``jax.experimental.topologies``
can describe a ``v5e:2x2`` host and lower + compile for one of its chips.
That catches what interpret mode cannot: block shapes the TPU tiling
rules refuse, kernels that overflow VMEM, programs that do not fit HBM.
Nothing runs, so these tests say nothing about results or times.

The topology is described in module-scoped fixtures — never at import
time — and all such tests live in this one file: only one process at a
time may load the TPU library, so under pytest-xdist exactly the worker
given this file loads it.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.chunked_paged_attn import paged_chunk_attention_kernel
from repro.models.registry import build_model

SDAR = get_config("sdar-8b")
H, KVH, D, PS = SDAR.n_heads, SDAR.n_kv_heads, SDAR.hd, SDAR.kv_page_size
# the largest chunk ModelBackend's adaptive prefill budget can hand out
# (ModelBackend caps target_bc at 8192 prompt tokens per tick)
MAX_PREFILL_CHUNK = 8192


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # no compiler logs outside
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache off meanwhile
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    if old_log is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = old_log


@pytest.fixture(scope="module")
def one_chip(topology):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topology.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(sharding, B, c, W, n_pages, dtype):
    args = (_spec(sharding, (B, c, H, D), dtype),
            _spec(sharding, (n_pages, PS, KVH, D), dtype),
            _spec(sharding, (n_pages, PS, KVH, D), dtype),
            _spec(sharding, (B, W), jnp.int32),
            _spec(sharding, (B,), jnp.int32))
    fn = functools.partial(paged_chunk_attention_kernel, interpret=False)
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("c", [1, 32])
def test_paged_kernel_compiles_for_decode(one_chip, c):
    """SDAR widths, 16-token pages, bf16 pool: the decode windows."""
    compiled = _compile_kernel(one_chip, B=8, c=c, W=128, n_pages=2048,
                               dtype=jnp.bfloat16)
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_kernel_compiles_for_largest_prefill_chunk(one_chip):
    """4·8192 query rows per KV head: the row-tile grid axis keeps the
    kernel's VMEM bounded whatever the chunk length."""
    compiled = _compile_kernel(one_chip, B=1, c=MAX_PREFILL_CHUNK, W=1024,
                               n_pages=2048, dtype=jnp.bfloat16)
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_step_compiles_with_kernel(one_chip):
    """One fused paged decode step of a 2-layer SDAR-width model, kernel
    compiled (``interpret=False``), pool donated."""
    cfg = SDAR.replace(n_layers=2)
    model = build_model(cfg)
    params = jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype),
        model.init(jax.random.PRNGKey(0), abstract=True))
    L, kvh, hd = model.paged_kv_dims()
    pool = {name: _spec(one_chip, (L, 512, PS, kvh, hd), cfg.cdt)
            for name in ("k_pages", "v_pages")}
    B, c, W = 8, 32, 64
    i32 = functools.partial(_spec, one_chip, dtype=jnp.int32)
    step = jax.jit(functools.partial(model.decode_step_paged, impl="kernel",
                                     interpret=False), donate_argnums=(1,))
    compiled = step.lower(params, pool, i32((B, c)), i32((B,)), i32((B,)),
                          i32((B, W)), i32((B,)), i32((B,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_split_kv_partial_compiles_for_four_chips(topology):
    """Split-KV attention over the four chips of the host, bf16 pool
    page-sharded on a ``kv`` mesh axis: the kernel runs per chip under
    ``shard_map`` and the partials merge with all-reduces."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.distributed.collectives import (KVShardSpec,
                                               split_kv_paged_partial)
    S, B, c, W, n_pages = 4, 8, 32, 64, 2048
    mesh = Mesh(np.array(topology.devices[:S]), ("kv",))
    rep = NamedSharding(mesh, PartitionSpec())
    pages = NamedSharding(mesh, PartitionSpec("kv"))
    fn = functools.partial(split_kv_paged_partial,
                           ks=KVShardSpec(mesh, S), interpret=False)
    compiled = jax.jit(fn).lower(
        _spec(rep, (B, c, H, D), jnp.bfloat16),
        _spec(pages, (n_pages, PS, KVH, D), jnp.bfloat16),
        _spec(pages, (n_pages, PS, KVH, D), jnp.bfloat16),
        _spec(rep, (B, W), jnp.int32), _spec(rep, (B,), jnp.int32),
        _spec(rep, (B,), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "all-reduce" in hlo

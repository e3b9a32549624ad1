"""Mixture-of-Experts FFN.

Two execution paths with identical semantics (modulo capacity dropping):

* ``moe_block_dense`` — one-hot einsum over all experts.  O(E·T·d·f) compute;
  only used as the oracle for tests and for tiny smoke configs.
* ``moe_block_sharded`` — production path: token-choice top-k routing, tokens
  sorted by expert id, per-expert grouped GEMM via ``lax.ragged_dot``, local
  experts per model-shard, partial outputs combined with ``psum``.  Runs under
  ``jax.shard_map`` (experts sharded over the ``model`` mesh axis, tokens over
  the data axes).  With a 1-device mesh it degenerates to the single-device
  sort-based path, which is also what unit tests exercise.

Token dropping: each model shard accepts at most
``capacity = ceil(T_local * top_k / n_model_shards * capacity_factor)``
(token, expert) pairs; overflow is dropped (standard GShard-style behaviour).
``capacity_factor <= 0`` disables dropping (capacity = T_local * top_k).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh, current_rules, shard
from repro.models.common import ArchConfig, dense_init_a
from repro.models.layers import _act


def init_moe(kg, cfg: ArchConfig, abstract=False):
    d, f, e = cfg.d_model, cfg.moe_ff, cfg.n_experts
    pd = cfg.pdt
    return {
        "router": dense_init_a(kg(), (d, e), pd, abstract=abstract),
        "w_gate": dense_init_a(kg(), (e, d, f), pd, fan_in=d, abstract=abstract),
        "w_up": dense_init_a(kg(), (e, d, f), pd, fan_in=d, abstract=abstract),
        "w_down": dense_init_a(kg(), (e, f, d), pd, fan_in=f, abstract=abstract),
    }


def axes_moe(cfg: ArchConfig):
    return {
        "router": ("embed", None),
        "w_gate": ("experts", "embed_p", "expert_mlp_p"),
        "w_up": ("experts", "embed_p", "expert_mlp_p"),
        "w_down": ("experts", "expert_mlp_p", "embed_p"),
    }


def _route(x, router_w, top_k: int):
    """x [T,d] → (gates [T,k] fp32, ids [T,k] int32). Gates renormalized."""
    logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.clip(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, ids.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Oracle path
# ---------------------------------------------------------------------------

def moe_block_dense(params, cfg: ArchConfig, x):
    """Reference MoE: [B,T,d] → [B,T,d] computing every expert densely."""
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
    gates, ids = _route(xf, params["router"], cfg.top_k)
    act = _act(cfg.act)
    cd = cfg.cdt
    h = jnp.einsum("td,edf->tef", xf, params["w_gate"].astype(cd))
    u = jnp.einsum("td,edf->tef", xf, params["w_up"].astype(cd))
    o = jnp.einsum("tef,efd->ted", act(h) * u, params["w_down"].astype(cd))
    onehot = jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.float32)   # [T,k,E]
    combine = jnp.einsum("tke,tk->te", onehot, gates)                # [T,E]
    out = jnp.einsum("ted,te->td", o.astype(jnp.float32), combine)
    return out.reshape(B, T, d).astype(x.dtype)


# ---------------------------------------------------------------------------
# Production path
# ---------------------------------------------------------------------------

def _moe_local(x, router_w, w_gate, w_up, w_down, *, cfg: ArchConfig,
               n_model: int, model_axis: str | None):
    """Per-shard body.  x [T_loc, d]; expert weights are the local slice."""
    T_loc, d = x.shape
    E_loc = w_gate.shape[0]
    k = cfg.top_k
    if model_axis is not None:
        mi = jax.lax.axis_index(model_axis)
    else:
        mi = 0
    lo = mi * E_loc

    gates, ids = _route(x, router_w, k)
    flat_ids = ids.reshape(-1)                                  # [T*k]
    flat_gates = gates.reshape(-1)
    tok_of = jnp.arange(T_loc * k, dtype=jnp.int32) // k

    if cfg.capacity_factor > 0:
        C = int(np.ceil(T_loc * k / max(n_model, 1) * cfg.capacity_factor))
        C = min(max(C, 1), T_loc * k)
    else:
        C = T_loc * k

    is_local = (flat_ids >= lo) & (flat_ids < lo + E_loc)
    sort_key = jnp.where(is_local, flat_ids, cfg.n_experts + 1)
    order = jnp.argsort(sort_key)[:C]
    sel_ids = flat_ids[order]
    sel_tok = tok_of[order]
    valid = is_local[order]

    rows = x[sel_tok] * valid[:, None].astype(x.dtype)
    gsz = jnp.sum(sel_ids[:, None] == (lo + jnp.arange(E_loc))[None, :],
                  axis=0).astype(jnp.int32)
    # overflow of the last group beyond C is implicitly dropped by argsort cut;
    # clamp group sizes so they sum to <= C.
    gsz = jnp.minimum(gsz, C - jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                                jnp.cumsum(gsz)[:-1]]))
    gsz = jnp.maximum(gsz, 0)

    cd = cfg.cdt
    act = _act(cfg.act)
    # NOTE: no preferred_element_type=f32 here — XLA hoists the implied
    # f32 conversion of the *stacked* expert weights out of the layer scan
    # (≈100 GiB of loop-invariant converts for Jamba/Kimi).  On TPU the MXU
    # accumulates bf16×bf16 in f32 natively; the output is cast below.
    h = jax.lax.ragged_dot(rows, w_gate.astype(cd), gsz)
    u = jax.lax.ragged_dot(rows, w_up.astype(cd), gsz)
    o = jax.lax.ragged_dot((act(h) * u).astype(cd), w_down.astype(cd), gsz)
    o = o.astype(jnp.float32) * (flat_gates[order] * valid)[:, None]
    y = jnp.zeros((T_loc, d), jnp.float32).at[sel_tok].add(o)
    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)
    return y.astype(x.dtype)


def moe_block_sharded(params, cfg: ArchConfig, x, *, model_axis="model"):
    """Production MoE: [B,T,d] → [B,T,d] under shard_map on the current mesh
    (tokens sharded over all non-model axes, experts over the model axis).

    Falls back to the single-shard sort-based path when no mesh is installed.
    """
    mesh = current_mesh()
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
    if mesh is None or model_axis not in mesh.axis_names:
        out = _moe_local(xf, params["router"], params["w_gate"],
                         params["w_up"], params["w_down"], cfg=cfg,
                         n_model=1, model_axis=None)
        return out.reshape(B, T, d)

    n_model = mesh.shape[model_axis]
    da = tuple(a for a in mesh.axis_names if a != model_axis)
    da_key = da if len(da) != 1 else da[0]
    body = functools.partial(_moe_local, cfg=cfg, n_model=n_model,
                             model_axis=model_axis)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(da_key, None), P(None, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=P(da_key, None),
        check_vma=False,
    )
    out = fn(xf, params["router"], params["w_gate"], params["w_up"],
             params["w_down"])
    return out.reshape(B, T, d)


def _moe_local_2d(x, router_w, w_gate, w_up, w_down, *, cfg: ArchConfig,
                  model_axis: str, data_axes: tuple):
    """2D expert-parallel body for serving: experts sharded over the model
    axis AND the expert-FFN dim sharded over the data axes (weights never
    gathered).  x is replicated (decode token counts are tiny); each device
    computes its (E_local × f_local) slice — gate/up produce [C, f_local],
    the down matmul yields an f-partial [C, d] summed with psum over data,
    and the per-expert scatter combines with psum over model."""
    T, d = x.shape
    E_loc = w_gate.shape[0]
    k = cfg.top_k
    n_model = jax.lax.axis_size(model_axis)
    mi = jax.lax.axis_index(model_axis)
    lo = mi * E_loc

    gates, ids = _route(x, router_w, k)
    flat_ids = ids.reshape(-1)
    flat_gates = gates.reshape(-1)
    tok_of = jnp.arange(T * k, dtype=jnp.int32) // k

    if cfg.capacity_factor > 0:
        C = int(np.ceil(T * k / max(n_model, 1) * cfg.capacity_factor))
        C = min(max(C, 1), T * k)
    else:
        C = T * k

    is_local = (flat_ids >= lo) & (flat_ids < lo + E_loc)
    order = jnp.argsort(jnp.where(is_local, flat_ids,
                                  cfg.n_experts + 1))[:C]
    sel_ids = flat_ids[order]
    sel_tok = tok_of[order]
    valid = is_local[order]
    rows = x[sel_tok] * valid[:, None].astype(x.dtype)
    gsz = jnp.sum(sel_ids[:, None] == (lo + jnp.arange(E_loc))[None, :],
                  axis=0).astype(jnp.int32)
    gsz = jnp.minimum(gsz, C - jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(gsz)[:-1]]))
    gsz = jnp.maximum(gsz, 0)

    cd = cfg.cdt
    act = _act(cfg.act)
    h = jax.lax.ragged_dot(rows, w_gate.astype(cd), gsz)   # [C, f_local]
    u = jax.lax.ragged_dot(rows, w_up.astype(cd), gsz)
    o = jax.lax.ragged_dot((act(h) * u).astype(cd), w_down.astype(cd), gsz)
    o = o.astype(jnp.float32) * (flat_gates[order] * valid)[:, None]
    y = jnp.zeros((T, d), jnp.float32).at[sel_tok].add(o)
    # sum f-partials over data AND per-expert partials over model
    y = jax.lax.psum(y, data_axes + (model_axis,))
    return y.astype(x.dtype)


def moe_block_2d(params, cfg: ArchConfig, x, *, model_axis="model"):
    """Serving MoE with 2D-sharded expert weights (see serving_rules)."""
    mesh = current_mesh()
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
    if mesh is None or model_axis not in mesh.axis_names:
        out = _moe_local(xf, params["router"], params["w_gate"],
                         params["w_up"], params["w_down"], cfg=cfg,
                         n_model=1, model_axis=None)
        return out.reshape(B, T, d)
    da = tuple(a for a in mesh.axis_names if a != model_axis)
    da_key = da if len(da) != 1 else da[0]
    body = functools.partial(_moe_local_2d, cfg=cfg, model_axis=model_axis,
                             data_axes=da)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, None), P(None, None),
                  P(model_axis, None, da_key), P(model_axis, None, da_key),
                  P(model_axis, da_key, None)),
        out_specs=P(None, None),
        check_vma=False,
    )
    out = fn(xf, params["router"], params["w_gate"], params["w_up"],
             params["w_down"])
    return out.reshape(B, T, d)


def moe_block(params, cfg: ArchConfig, x, *, force_dense: bool = False):
    if force_dense or (cfg.n_experts <= 8 and current_mesh() is None):
        return moe_block_dense(params, cfg, x)
    rules = current_rules()
    if rules is not None and rules.table.get("moe_mode") == "2d":
        return moe_block_2d(params, cfg, x)
    return moe_block_sharded(params, cfg, x)

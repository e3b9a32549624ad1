"""The registered dispatch inventory Pass 1 audits.

Every jit the serving hot path can execute is enumerated here, twice over:

* :func:`build_entries` constructs each dispatch on a tiny audit model
  (distinctive ``vocab_size`` so a vocab axis is unambiguous in shapes)
  together with its declared expectations — donation alias count, host-
  transfer budget, analytic collective volume, recompile buckets;
* :data:`KNOWN_JIT_SITES` registers every ``jax.jit`` construction site in
  the serving modules.  :func:`audit_registration` AST-scans those modules
  and fails (HLO006) on any unregistered site — a new jit cannot ship
  without either an inventory entry or an explicit registration.

Entries are audited at ``kv_shards == 1`` in-process and ``kv_shards == 2``
when ≥ 2 devices are visible (``check.py`` forces 8 virtual host devices).
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Any, Callable

from repro.analysis.findings import Finding

# Audit model: small enough to compile in seconds on CPU, vocab chosen so
# no other dimension (d_model, d_ff, pages, page_size, batch, chunk) can
# collide with it — a 307 in any shape IS the vocab axis.
AUDIT_ARCH = dict(name="audit", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=307,
                  block_size=8, confidence_threshold=0.6)
AUDIT_B = 2          # batch rows in the audited dispatch
AUDIT_C = 4          # decode window (chunk) width
AUDIT_T = 8          # prefill token width


@dataclass
class ChurnSpec:
    """Tick shape grid for HLO005: each maker returns (args, kwargs) for
    one raw shape, routed through the backend's bucketing."""
    arg_makers: list
    declared_buckets: int


@dataclass
class DispatchEntry:
    name: str
    kv_shards: int
    fn: Any                              # the jitted callable
    make_args: Callable[[], tuple]       # fresh args (donation-safe)
    make_kwargs: Callable[[], dict] = field(default=lambda: {})
    traceable: Any = None                # callable for jax.make_jaxpr
    min_aliases: int | None = 2          # None → skip HLO001
    vocab_size: int | None = None        # None → skip HLO002
    host_budget_bytes: int | None = None  # None → skip HLO003
    expected_collectives: dict | None = None  # None → skip HLO004
    churn: ChurnSpec | None = None       # None → skip HLO005

    @property
    def target(self) -> str:
        return f"{self.name}@kv{self.kv_shards}"


def build_entries(kv_shards: int = 1) -> list:
    """Construct the dispatch inventory on the audit model.

    Requires jax; with ``kv_shards > 1`` the process must already see at
    least that many devices (check.py sets XLA_FLAGS before importing).
    """
    import jax
    import jax.numpy as jnp

    from repro.models import ArchConfig, build_model
    from repro.serving.backends import (ModelBackend,
                                        _split_kv_collective_bytes)

    cfg = ArchConfig(**AUDIT_ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    be = ModelBackend(model, params, n_slots=8, max_len=64,
                      attn_impl="ref", kv_shards=kv_shards)
    B, c, T, W = AUDIT_B, AUDIT_C, AUDIT_T, be._table_width
    V = cfg.vocab_size
    S = kv_shards
    i32 = jnp.int32

    def cache():
        # fresh zero pool per call: donation-safe under real execution
        # (the allocator's own handles must never be consumed by the audit)
        return {"k_pages": jnp.zeros_like(be.kv.k_pages),
                "v_pages": jnp.zeros_like(be.kv.v_pages)}

    def shard_kw(b):
        return ({"shard_offs": jnp.zeros(b, i32)} if S > 1 else {})

    def decode_args(b, ch):
        return (params, cache(), jnp.zeros((b, ch), i32),
                jnp.zeros(b, i32), jnp.zeros(b, i32),
                jnp.zeros((b, W), i32), jnp.zeros(b, i32),
                jnp.zeros(b, i32))

    def prefill_args(b, t):
        return (params, cache(), jnp.zeros((b, t), i32),
                jnp.zeros(b, i32), jnp.zeros((b, W), i32))

    def chunk_args(b, t):
        return (params, cache(), jnp.zeros((b, t), i32),
                jnp.zeros(b, i32), jnp.zeros(b, i32),
                jnp.zeros((b, W), i32))

    # analytic cross-shard model, expressed per device: the ring model
    # counts 2·(S−1) payload hops per reduction; the per-device HLO
    # operand volume is the payload itself, once per attention layer.
    n_attn = sum(1 for i in range(cfg.n_layers) if cfg.is_attn_layer(i))

    def expected_allreduce(tokens):
        if S <= 1:
            return {}
        wire = _split_kv_collective_bytes(S, n_attn, cfg.n_heads, cfg.hd,
                                          B, tokens)
        return {"all-reduce": wire // (2 * (S - 1))}

    entries = [
        DispatchEntry(
            name="decode_step_paged", kv_shards=S, fn=be._decode_paged,
            make_args=lambda: decode_args(B, c),
            make_kwargs=lambda: shard_kw(B),
            vocab_size=V,
            host_budget_bytes=8 * B * c,        # conf fp32 + tok int32
            expected_collectives=expected_allreduce(c),
            churn=None if S > 1 else ChurnSpec(
                # raw tick batches 1..4 bucket to {1, 2, 4}: three traces
                arg_makers=[
                    (lambda b=b: (decode_args(be._bucket(b), c),
                                  shard_kw(be._bucket(b))))
                    for b in (1, 2, 3, 4)],
                declared_buckets=3),
        ),
        DispatchEntry(
            name="prefill_paged", kv_shards=S, fn=be._prefill_paged,
            make_args=lambda: prefill_args(B, T),
            vocab_size=V,
            host_budget_bytes=8 * B,            # [B] conf + [B] tok
            # wave prefill only scatters into the pool (no paged-prefix
            # read) — no cross-shard merge, so no collectives even sharded
            expected_collectives={},
        ),
        DispatchEntry(
            name="prefill_chunk_paged", kv_shards=S, fn=be._prefill_chunk,
            make_args=lambda: chunk_args(B, T),
            make_kwargs=lambda: shard_kw(B),
            vocab_size=V,
            host_budget_bytes=8 * B,            # [B] conf + [B] tok
            expected_collectives=expected_allreduce(T),
        ),
    ]

    if S == 1:
        from repro.models.transformer import copy_pages, write_pages
        copy_jit = jax.jit(copy_pages, donate_argnums=(0,))
        write_jit = jax.jit(write_pages, donate_argnums=(0,))
        k_shape = be.kv.k_pages.shape          # [L, P, page, KVH, hd]
        host_block = (k_shape[0], 4) + k_shape[2:]
        entries += [
            DispatchEntry(
                name="copy_pages", kv_shards=S, fn=copy_jit,
                make_args=lambda: (cache(), jnp.zeros(4, i32),
                                   jnp.zeros(4, i32)),
                vocab_size=V, host_budget_bytes=0,
                expected_collectives={},
            ),
            DispatchEntry(
                name="write_pages", kv_shards=S, fn=write_jit,
                make_args=lambda: (cache(), jnp.zeros(4, i32),
                                   jnp.zeros(host_block, jnp.float32),
                                   jnp.zeros(host_block, jnp.float32)),
                vocab_size=V, host_budget_bytes=0,
                expected_collectives={},
            ),
        ]
    return entries


# ---------------------------------------------------------------------------
# HLO006 — jit-site registration
# ---------------------------------------------------------------------------

# Modules whose jax.jit sites must be registered (paths relative to repo
# root).  Adding a jit to any of these without registering it here makes
# `python -m repro.analysis.check` fail.
SCANNED_MODULES = (
    "src/repro/serving/backends.py",
    "src/repro/serving/kv_pool.py",
    "src/repro/models/transformer.py",
    "src/repro/distributed/collectives.py",
    "src/repro/kernels/ops.py",
)

# (module, enclosing qualname, jitted-callable descriptor).  The descriptor
# is the root callee of the jit's first argument (through functools.partial)
# or "@jax.jit" for decorator sites.
KNOWN_JIT_SITES = {
    ("src/repro/serving/backends.py", "ModelBackend.__init__",
     "model.prefill_paged"),
    ("src/repro/serving/backends.py", "ModelBackend.__init__",
     "model.prefill_chunk_paged"),
    ("src/repro/serving/backends.py", "ModelBackend.__init__",
     "model.decode_step_paged"),
    ("src/repro/serving/backends.py", "ModelBackend.__init__",
     "model.chunk_forward"),
    ("src/repro/serving/backends.py", "ModelBackend.__init__",
     "model.advance_states"),
    ("src/repro/serving/backends.py", "ModelBackend.__init__",
     "self._prefill_impl"),
    ("src/repro/serving/backends.py", "ModelBackend.__init__",
     "self._merge_impl"),
    ("src/repro/serving/kv_pool.py", "PagedKVAllocator._device_copy",
     "copy_pages"),
    ("src/repro/serving/kv_pool.py", "PagedKVAllocator._swap_in_device",
     "write_pages"),
    ("src/repro/serving/kv_pool.py", "PagedKVAllocator.init_storage",
     "<lambda>"),
    ("src/repro/kernels/ops.py", "<module>", "softmax_confidence_device"),
    ("src/repro/kernels/ops.py", "paged_chunk_attention", "@jax.jit"),
    ("src/repro/kernels/ops.py", "paged_chunk_attention_full", "@jax.jit"),
    ("src/repro/kernels/ops.py", "block_diffusion_attention", "@jax.jit"),
}


def repo_root() -> str:
    return os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))


def _is_jax_jit(node) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "jit"
            and isinstance(node.value, ast.Name)
            and node.value.id == "jax")


def _root_callee(node) -> str:
    """Descriptor of the callable being jitted: unwrap functools.partial
    (and ``named_partial``), name lambdas, unparse dotted names."""
    if isinstance(node, ast.Call):
        fn = node.func
        is_partial = (isinstance(fn, ast.Name)
                      and fn.id in ("partial", "named_partial")) or \
            (isinstance(fn, ast.Attribute) and fn.attr == "partial")
        if is_partial and node.args:
            return _root_callee(node.args[0])
        return ast.unparse(node)
    if isinstance(node, ast.Lambda):
        return "<lambda>"
    return ast.unparse(node)


def scan_jit_sites(root: str | None = None) -> list:
    """All jax.jit construction sites in SCANNED_MODULES →
    [(module, qualname, descriptor, lineno), ...]."""
    root = root or repo_root()
    sites = []
    for rel in SCANNED_MODULES:
        path = os.path.join(root, rel)
        with open(path) as f:
            tree = ast.parse(f.read(), filename=rel)

        def walk(node, qual):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    for dec in child.decorator_list:
                        if _is_jax_jit(dec) or (
                                isinstance(dec, ast.Call) and (
                                    _is_jax_jit(dec.func)
                                    or any(_is_jax_jit(a)
                                           for a in dec.args))):
                            sites.append((rel, child.name, "@jax.jit",
                                          child.lineno))
                    walk(child, f"{qual}.{child.name}"
                         if qual != "<module>" else child.name)
                elif isinstance(child, ast.ClassDef):
                    walk(child, f"{qual}.{child.name}"
                         if qual != "<module>" else child.name)
                else:
                    for sub in ast.walk(child):
                        if isinstance(sub, ast.Call) \
                                and _is_jax_jit(sub.func):
                            desc = (_root_callee(sub.args[0])
                                    if sub.args else "<no-arg>")
                            sites.append((rel, qual, desc, sub.lineno))

        walk(tree, "<module>")
    return sites


def audit_registration(root: str | None = None) -> list:
    """HLO006: every scanned jit site must be in KNOWN_JIT_SITES."""
    out = []
    for rel, qual, desc, lineno in scan_jit_sites(root):
        if (rel, qual, desc) not in KNOWN_JIT_SITES:
            out.append(Finding(
                "HLO006", f"{rel}:{lineno}",
                f"unregistered jax.jit site in {qual}: jitted callable "
                f"{desc!r} — add it to the dispatch inventory "
                f"(repro.analysis.inventory.KNOWN_JIT_SITES) so the "
                f"compiled-artifact audit covers it"))
    return out

"""Jaxpr-level walker: byte accounting + intermediate-aval census.

The HLO pass (:mod:`repro.analysis.hlo`) audits what XLA *compiled*; this
walker audits what was *traced* — before fusion/DCE can hide an
intermediate.  Two uses:

* byte accounting per primitive equation (Σ operand + result aval bytes,
  recursing through ``pjit``/``custom_*`` call wrappers and multiplying
  ``scan`` bodies by their trip count) — property-tested against XLA's own
  ``compiled.cost_analysis()['bytes accessed']`` on graphs where both are
  exact (single primitives: XLA counts precisely operands + results);
* the vocab-escape census: every eqn outvar's aval, so a rule can assert
  no ``[B, c, V]``-sized value is still live at the jaxpr boundary.
"""

from __future__ import annotations

from jax.extend import core as jax_core

# Call-like primitives whose inner jaxpr should be walked transparently
# (the wrapper eqn itself moves no bytes).
_CALL_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr",
                "body_jaxpr")


def _inner_jaxprs(eqn):
    """(closed_jaxpr, trip_multiplier) pairs reachable from one eqn."""
    out = []
    params = eqn.params
    if eqn.primitive.name == "scan":
        out.append((params["jaxpr"], int(params["length"])))
        return out
    if eqn.primitive.name == "while":
        # trip count is data-dependent at trace time; callers that need
        # exact totals should audit the compiled HLO (known_trip_count)
        out.append((params["body_jaxpr"], None))
        out.append((params["cond_jaxpr"], None))
        return out
    if eqn.primitive.name == "cond":
        for br in params.get("branches", ()):
            out.append((br, None))
        return out
    for key in _CALL_PARAMS:
        if key in params:
            out.append((params[key], 1))
    return out


def _as_jaxpr(obj):
    return obj.jaxpr if isinstance(obj, jax_core.ClosedJaxpr) else obj


def _is_call(eqn) -> bool:
    return bool(_inner_jaxprs(eqn))


def iter_eqns(closed, mult: float = 1.0):
    """Yield ``(eqn, trip_multiplier)`` for every *primitive* equation,
    recursing through call wrappers; ``trip_multiplier`` is None when an
    enclosing while's trip count is unknown at trace time."""
    for eqn in _as_jaxpr(closed).eqns:
        inner = _inner_jaxprs(eqn)
        if inner:
            for sub, m in inner:
                sub_mult = None if (m is None or mult is None) \
                    else mult * m
                yield from iter_eqns(sub, sub_mult)
        else:
            yield eqn, mult


def _aval_bytes(aval) -> int:
    try:
        return int(aval.size) * aval.dtype.itemsize
    except (AttributeError, TypeError):
        return 0


def _var_bytes(v) -> int:
    if isinstance(v, jax_core.Literal):
        return 0 if getattr(v.val, "ndim", 0) == 0 else _aval_bytes(v.aval)
    return _aval_bytes(v.aval)


def byte_traffic(closed) -> float:
    """Σ over primitive eqns of (operand + result aval bytes), scan bodies
    multiplied by trip count.  Returns ``float('nan')`` if an unknown-trip
    while loop makes the total undefined."""
    total = 0.0
    for eqn, mult in iter_eqns(closed):
        if mult is None:
            return float("nan")
        total += mult * (sum(_var_bytes(v) for v in eqn.invars)
                         + sum(_aval_bytes(v.aval) for v in eqn.outvars))
    return total


def intermediate_avals(closed):
    """All eqn-output avals across the whole (nested) jaxpr."""
    out = []
    for eqn, _ in iter_eqns(closed):
        out.extend(v.aval for v in eqn.outvars)
    return out


def out_avals(closed):
    return list(closed.out_avals)

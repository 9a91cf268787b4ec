"""PRNG-stream lint: key-derivation-graph checks over jaxprs.

Two bug classes this repo has actually shipped and fixed by hand:

- **Key reuse** — the same key value consumed by two independent
  sampling/derivation sites. Every jax key is single-use: consuming it
  twice correlates the two streams bit-for-bit.
- **Batch-position-dependent streams** — ``split(key, b)`` feeding
  per-item streams (the PR-5 eval bug): item i's randomness then depends
  on its POSITION in the batch, so re-chunking or re-batching changes
  results. Per-identity ``fold_in(key, item_id)`` is the repo idiom.

The lint traces a callable to its jaxpr and walks the key-flow graph.
Typed keys (``jax.random.key``) appear as first-class ``key<fry>``
arrays flowing through ``random_split`` / ``random_fold_in`` /
``random_bits`` primitives — but *inside* sub-jaxprs (`jax.random.
uniform` wraps its body in a named ``pjit``), so the walker recurses
through pjit/scan/cond/while bodies carrying variable identity across
the call boundary. Legacy raw ``uint32[2]`` keys surface as
``threefry2x32`` consumption. ``core/threefry.py``'s bit-exact replica
computes with plain uint32 arithmetic and is invisible here by design —
its stream discipline is pinned by tests/test_threefry.py instead.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
from jax.extend import core as jcore

# primitives that CONSUME a key operand (derivation or sampling); a key
# hitting two of these is used twice
KEY_CONSUMERS = frozenset({
    "random_bits", "random_fold_in", "random_split", "threefry2x32",
})

# primitives that pass the SAME logical key array through unchanged
_PASSTHROUGH = frozenset({
    "reshape", "transpose", "convert_element_type", "copy",
    "copy_p", "device_put",
})


@dataclasses.dataclass(frozen=True)
class KeyFinding:
    kind: str           # "key-reuse" | "batch-split"
    primitive: str
    message: str

    def __str__(self):
        return f"{self.kind}: {self.message}"


def _is_key_var(v) -> bool:
    aval = getattr(v, "aval", None)
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return False
    try:
        return jax.dtypes.issubdtype(dtype, jax.dtypes.prng_key)
    except Exception:
        return False


def _sub_jaxprs(params: dict) -> list[Any]:
    found = []
    kinds = (jcore.Jaxpr, jcore.ClosedJaxpr)
    for val in params.values():
        if isinstance(val, kinds):
            found.append(val)
        elif isinstance(val, (tuple, list)):
            found.extend(x for x in val if isinstance(x, kinds))
    return found


def _inner(j):
    return j.jaxpr if hasattr(j, "jaxpr") else j


class _Walker:
    def __init__(self):
        self.alias: dict[Any, Any] = {}
        self.consumers: dict[Any, list[str]] = {}
        self.splits: list[tuple[str, int]] = []

    def root(self, v):
        seen = []
        while v in self.alias:
            seen.append(v)
            v = self.alias[v]
        for s in seen:
            self.alias[s] = v
        return v

    def _consume(self, v, prim: str):
        if isinstance(v, jcore.Literal):
            return
        self.consumers.setdefault(self.root(v), []).append(prim)

    def walk(self, jaxpr):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            subs = _sub_jaxprs(eqn.params)
            if subs:
                args = list(eqn.invars)
                for sub in subs:
                    inner = _inner(sub)
                    # map call-boundary operands onto the body's invars so
                    # key identity survives pjit/scan/cond inlining; when
                    # the arities don't line up (while-loop const split),
                    # the body's keys become fresh roots — conservative,
                    # never a false positive
                    if len(inner.invars) == len(args):
                        pairs = zip(inner.invars, args)
                    elif len(inner.invars) == len(args) - 1:
                        pairs = zip(inner.invars, args[1:])   # cond pred
                    else:
                        pairs = ()
                    for iv, ov in pairs:
                        if (_is_key_var(iv)
                                and not isinstance(ov, jcore.Literal)):
                            self.alias[iv] = self.root(ov)
                    self.walk(inner)
                    if len(inner.outvars) == len(eqn.outvars):
                        for outer, inner_out in zip(eqn.outvars,
                                                    inner.outvars):
                            if (_is_key_var(outer)
                                    and not isinstance(inner_out,
                                                       jcore.Literal)):
                                self.alias[outer] = self.root(inner_out)
                continue
            if prim in KEY_CONSUMERS:
                if prim == "threefry2x32":
                    # legacy raw keys: the two uint32 halves are operands
                    # 0-1; count each distinct var once
                    for v in dict.fromkeys(eqn.invars[:2]):
                        self._consume(v, prim)
                else:
                    for v in eqn.invars:
                        if _is_key_var(v):
                            self._consume(v, prim)
                if prim == "random_split":
                    shape = eqn.params.get("shape", ())
                    self.splits.append((prim, math.prod(shape)))
                continue
            if prim in _PASSTHROUGH and len(eqn.outvars) == 1:
                src = eqn.invars[0]
                if (_is_key_var(eqn.outvars[0])
                        and not isinstance(src, jcore.Literal)):
                    self.alias[eqn.outvars[0]] = self.root(src)


def lint_jaxpr(closed_jaxpr) -> list[KeyFinding]:
    """All PRNG findings in a (closed) jaxpr, sub-jaxprs included."""
    w = _Walker()
    w.walk(_inner(closed_jaxpr))
    findings = []
    for var, prims in w.consumers.items():
        if len(prims) > 1:
            findings.append(KeyFinding(
                "key-reuse", prims[0],
                f"key {var} consumed {len(prims)} times "
                f"({', '.join(prims)}): every consumption after the first "
                f"reuses the same stream"))
    for prim, count in w.splits:
        if count > 2:
            findings.append(KeyFinding(
                "batch-split", prim,
                f"split(key, {count}) creates batch-position-dependent "
                f"streams; per-item fold_in(key, item_id) keeps results "
                f"invariant to batching"))
    return findings


def lint_fn(fn, *args, **kwargs) -> list[KeyFinding]:
    """Trace ``fn(*args, **kwargs)`` and lint its key-derivation graph.

    Keyword arguments are bound via ``functools.partial`` before tracing
    (so static/config kwargs work unchanged)."""
    if kwargs:
        fn = functools.partial(fn, **kwargs)
    return lint_jaxpr(jax.make_jaxpr(fn)(*args))


def check_fn(fn, *args, allow_batch_splits: int = 0,
             **kwargs) -> list[KeyFinding]:
    """Lint and filter: key reuse is never allowed; up to
    ``allow_batch_splits`` batch-split sites are (the training scan
    legitimately splits its step and init keys — batching there IS the
    semantics; eval/serving paths must be chunk-invariant and allow 0).
    """
    findings = lint_fn(fn, *args, **kwargs)
    reuse = [f for f in findings if f.kind == "key-reuse"]
    splits = [f for f in findings if f.kind == "batch-split"]
    return reuse + splits[allow_batch_splits:]

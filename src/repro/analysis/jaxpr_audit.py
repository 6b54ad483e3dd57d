"""Jaxpr auditor: static proofs over the closed jaxprs of entry points.

The paper's efficiency argument (§1.1, §3–§4) is that the Eq.-3/4
regularizer and the streaming graph construction never materialize a dense
B×B (or N×M) intermediate outside a Pallas kernel, and that the training
scan stays free of host syncs.  This pass walks the *traced* jaxpr of each
registered entry point (no execution) and enforces exactly that:

  * ``J001`` — any intermediate at or above a byte threshold produced
    outside a ``pallas_call`` (the generalized form of the historical
    ``count_bxb_intermediates`` spot check);
  * ``J002`` — (B, B)-shaped intermediates beyond the entry's declared
    budget (0 for every fused path; the jnp reference is kept as a canary
    that must still trip the counter — ``J000`` fires if it stops doing
    so, i.e. if the counter itself broke);
  * ``J003`` — silent dtype promotion: float64 anywhere, or widening
    ``convert_element_type`` on non-scalars out of a declared
    low-precision compute dtype (bf16 paths leaking f32);
  * ``J004`` — host callbacks / sync primitives inside scan or while
    bodies (a ``debug_print`` in the engine's scan body would serialize
    every step on a host round-trip);
  * ``J005`` — the engine's chunk jit must donate every carry leaf
    (``donated_invars`` of the named jit call);
  * ``J006`` — large arrays captured as jaxpr *constants* (closure
    capture silently bakes weights into the executable and re-traces on
    every new array identity) instead of arriving as arguments.

``count_bxb_intermediates`` lives here now (moved from
``benchmarks/bench_kernels.py``; the bench re-exports it) with identical
semantics — benchmarks, tests, and the audit share one counter.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator

import jax
import numpy as np

from repro.analysis.findings import Finding

__all__ = [
    "EntryPoint",
    "count_bxb_intermediates",
    "audit_entry",
    "trace_entry",
    "iter_eqns",
    "is_jit_call",
]

#: Primitives that imply a host round-trip or synchronization; inside a
#: scan/while body each occurrence stalls the whole compiled loop.
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
    "host_callback_call", "outside_call", "infeed", "outfeed",
    "copy_to_host",
})

_FLOAT_WIDTH = {"bfloat16": 2, "float16": 2, "float32": 4, "float64": 8}


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    """One audited entry point: how to trace it and what to expect.

    ``build()`` returns ``(fn, args)``; the auditor only traces
    (``jax.make_jaxpr``), it never executes the function.  All thresholds
    are part of the committed registry, so "no unexpected dense growth" is
    a reviewable contract, not a magic constant.
    """

    name: str
    build: Callable[[], tuple[Callable, tuple]]
    #: Exact-shape (B, B) budget: ``B`` enables the counter, ``expect_bxb``
    #: is the allowed count (None = informational only, e.g. the jnp
    #: reference canary).
    B: int | None = None
    expect_bxb: int | None = 0
    #: The reference canary must still *trip* the counter at >= this many
    #: (guards the counter itself against silent breakage).
    canary_min_bxb: int | None = None
    #: J001 byte threshold for any single intermediate outside Pallas.
    dense_bytes: int = 1 << 20
    #: Declared low-precision compute dtype ("bfloat16") for J003, or None.
    compute_dtype: str | None = None
    allow_f64: bool = False
    #: (jit name, n leading flat invars that must be donated) for J005;
    #: n=None derives the count from the first build() arg (the carry tree).
    donate: tuple[str, int | None] | None = None
    #: J006 threshold for captured constants.
    const_bytes: int = 1 << 20
    #: Mesh axis names this entry is contracted to run under; collectives
    #: binding any other axis flag S001.  None = single-host contract.
    mesh_axes: tuple[str, ...] | None = None
    #: Under the bit-reproducibility contract (D001 applies)?  Entries
    #: that legitimately tolerate last-ulp drift opt out explicitly.
    deterministic: bool = True
    #: Collectives tolerated inside scan/while bodies (S002); reductions
    #: keep their operand shape, gathers do not — hence the default.
    allow_loop_collectives: tuple[str, ...] = ("psum",)


def is_jit_call(eqn) -> bool:
    """A nested ``jax.jit`` call, recognized by its parameters (a closed
    sub-jaxpr plus per-operand donation flags) rather than by primitive
    name, which JAX has renamed between releases."""
    return "jaxpr" in eqn.params and "donated_invars" in eqn.params


def iter_eqns(jaxpr, *, in_loop: bool = False
              ) -> Iterator[tuple[Any, bool]]:
    """Yield ``(eqn, in_loop)`` over ``jaxpr`` and every sub-jaxpr,
    *except* the bodies of ``pallas_call`` eqns (what a kernel does
    tile-by-tile in VMEM is precisely what the dense rules must not see).
    ``in_loop`` is True inside scan/while bodies.
    """
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        if eqn.primitive.name == "pallas_call":
            continue
        inner_loop = in_loop or eqn.primitive.name in ("scan", "while")
        for p in eqn.params.values():
            sub = None
            if hasattr(p, "eqns"):            # open Jaxpr
                sub = p
            elif hasattr(p, "jaxpr"):         # ClosedJaxpr
                sub = p.jaxpr
            if sub is not None:
                yield from iter_eqns(sub, in_loop=inner_loop)
            elif isinstance(p, (tuple, list)):
                for q in p:
                    if hasattr(q, "eqns"):
                        yield from iter_eqns(q, in_loop=inner_loop)
                    elif hasattr(q, "jaxpr"):
                        yield from iter_eqns(q.jaxpr, in_loop=inner_loop)


def _live_outvars(eqn):
    return [v for v in eqn.outvars if not isinstance(v, jax.core.DropVar)]


def count_bxb_intermediates(fn, *args, B: int) -> int:
    """Number of (B, B)-shaped values produced outside Pallas kernels in
    ``fn``'s jaxpr (descending through jit/custom_vjp calls; a value coming
    straight out of a ``pallas_call`` does not count — the kernel produced
    it tile by tile)."""
    closed = jax.make_jaxpr(fn)(*args)
    return _count_bxb(closed.jaxpr, B)


def _count_bxb(jaxpr, B: int) -> int:
    n = 0
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name in ("pallas_call", "broadcast_in_dim"):
            # Kernel output, or a constant splat (e.g. a zero cotangent) —
            # neither is a materialized product.
            continue
        live = _live_outvars(eqn)
        if not live:
            continue   # dead outputs — DCE removes them before they exist
        if any(hasattr(p, "eqns") or hasattr(p, "jaxpr")
               for p in eqn.params.values()):
            continue   # call-like eqn: outvars just re-bind inner results
        n += sum(1 for v in live
                 if getattr(v.aval, "shape", None) == (B, B))
    return n


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * dtype.itemsize


def trace_entry(entry: EntryPoint):
    """The entry's closed jaxpr (shared across the jaxpr-walking passes
    so each entry is traced once per CLI run)."""
    fn, args = entry.build()
    return jax.make_jaxpr(fn)(*args)


def audit_entry(entry: EntryPoint, closed: Any | None = None
                ) -> tuple[list[Finding], dict]:
    """Trace ``entry`` (or reuse a shared trace) and return
    ``(findings, metrics)``."""
    fn, args = entry.build()
    if closed is None:
        closed = jax.make_jaxpr(fn)(*args)
    findings: list[Finding] = []
    metrics: dict = {}

    # -- J002 / J000: the exact (B, B) counter --------------------------
    if entry.B is not None:
        n_bxb = _count_bxb(closed.jaxpr, entry.B)
        metrics["bxb_outside_kernels"] = n_bxb
        if entry.expect_bxb is not None and n_bxb > entry.expect_bxb:
            findings.append(Finding(
                "jaxpr", "J002", entry.name,
                f"{n_bxb} (B, B) intermediates outside Pallas kernels "
                f"(budget {entry.expect_bxb}, B={entry.B})",
                detail=f"bxb>{entry.expect_bxb}"))
        if entry.canary_min_bxb is not None and n_bxb < entry.canary_min_bxb:
            findings.append(Finding(
                "jaxpr", "J000", entry.name,
                f"reference canary counted only {n_bxb} (B, B) "
                f"intermediates (expected >= {entry.canary_min_bxb}) — the "
                "counter itself no longer sees dense intermediates",
                detail="canary"))

    # -- Per-eqn rules ---------------------------------------------------
    max_bytes = 0
    dense_hits: dict[str, int] = {}
    promo_hits: dict[str, int] = {}
    callback_hits: dict[str, int] = {}
    donated_ok: bool | None = None
    donate_name, donate_n = entry.donate or (None, 0)
    if donate_name is not None and donate_n is None:
        donate_n = len(jax.tree_util.tree_leaves(args[0]))
    for eqn, in_loop in iter_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        if prim == "pallas_call":
            continue
        if in_loop and prim in CALLBACK_PRIMITIVES:
            callback_hits[prim] = callback_hits.get(prim, 0) + 1
        if donate_name is not None and is_jit_call(eqn) \
                and eqn.params.get("name") == donate_name:
            donated = eqn.params.get("donated_invars", ())
            donated_ok = (len(donated) >= donate_n
                          and all(donated[:donate_n]))
        live = _live_outvars(eqn)
        call_like = any(hasattr(p, "eqns") or hasattr(p, "jaxpr")
                        for p in eqn.params.values())
        for v in live:
            nbytes = _aval_bytes(v.aval)
            max_bytes = max(max_bytes, nbytes)
            if (not call_like and prim != "broadcast_in_dim"
                    and nbytes >= entry.dense_bytes):
                key = f"{prim}:{tuple(v.aval.shape)}"
                dense_hits[key] = dense_hits.get(key, 0) + 1
            dt = getattr(v.aval, "dtype", None)
            if dt is not None and dt.name == "float64" \
                    and not entry.allow_f64 and not call_like:
                promo_hits["float64"] = promo_hits.get("float64", 0) + 1
        if prim == "convert_element_type" and entry.compute_dtype:
            src = getattr(eqn.invars[0].aval, "dtype", None)
            dst = getattr(eqn.outvars[0].aval, "dtype", None)
            if (src is not None and dst is not None
                    and src.name == entry.compute_dtype
                    and _FLOAT_WIDTH.get(dst.name, 0)
                    > _FLOAT_WIDTH.get(src.name, 9)
                    and getattr(eqn.outvars[0].aval, "shape", ())):
                key = f"{src.name}->{dst.name}"
                promo_hits[key] = promo_hits.get(key, 0) + 1

    metrics["max_intermediate_bytes"] = max_bytes
    for key, count in sorted(dense_hits.items()):
        findings.append(Finding(
            "jaxpr", "J001", entry.name,
            f"{count}x dense intermediate {key} "
            f">= {entry.dense_bytes} bytes outside Pallas kernels",
            detail=key))
    for key, count in sorted(promo_hits.items()):
        findings.append(Finding(
            "jaxpr", "J003", entry.name,
            f"{count}x silent dtype promotion ({key})", detail=key))
    for prim, count in sorted(callback_hits.items()):
        findings.append(Finding(
            "jaxpr", "J004", entry.name,
            f"{count}x host callback/sync primitive '{prim}' inside a "
            "scan/while body", detail=prim))
    if donate_name is not None:
        metrics["carry_donated"] = bool(donated_ok)
        if donated_ok is None:
            findings.append(Finding(
                "jaxpr", "J005", entry.name,
                f"could not find jit call named {donate_name!r} to verify "
                "carry donation", detail=f"{donate_name}:missing"))
        elif not donated_ok:
            findings.append(Finding(
                "jaxpr", "J005", entry.name,
                f"jit {donate_name!r} does not donate all "
                f"{donate_n} carry leaves", detail=donate_name))

    # -- J006: captured constants ---------------------------------------
    big_consts = [c for c in closed.consts
                  if getattr(c, "nbytes", 0) >= entry.const_bytes]
    metrics["captured_const_bytes"] = int(
        sum(getattr(c, "nbytes", 0) for c in closed.consts))
    for c in big_consts:
        findings.append(Finding(
            "jaxpr", "J006", entry.name,
            f"array of shape {tuple(np.shape(c))} ({c.nbytes} bytes) "
            "captured as a jaxpr constant — pass it as an argument",
            detail=f"const:{tuple(np.shape(c))}"))
    return findings, metrics

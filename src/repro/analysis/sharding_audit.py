"""Sharding / collective auditor (S-pass).

The multi-host roadmap items (elastic ``sync_mesh`` membership, sharded
graph construction) will layer explicit collectives over the audited
entry points.  This pass is the gate that work builds against: it walks
each audited jaxpr and checks every collective against the entry's
*declared* mesh contract (``EntryPoint.mesh_axes``):

  * ``S001`` — a collective referencing an axis name outside the entry's
    declared mesh axes.  An undeclared axis either crashes at dispatch
    (late, on the big machine) or silently binds to a vmap axis with
    different semantics.  Entries with no ``mesh_axes`` declaration are
    single-host contracts: *any* named collective inside them flags.
  * ``S002`` — a gathering collective (``all_gather`` / ``all_to_all``)
    inside a scan/while body that the entry did not opt into
    (``EntryPoint.allow_loop_collectives``, default allows only the
    reduction ``psum``).  A gather in a loop body re-materializes the
    gathered operand every step — the "implicit resharding" failure mode
    where a sharded carry silently round-trips through HBM per step.
  * ``S003`` — a donation-annotated jit whose donated carry leaf has
    *explicit but different* input and output shardings.  Donation
    aliases the output buffer onto the input; mismatched shardings force
    XLA to silently copy instead, defeating the donation the J005 pass
    already proved present.  Unspecified shardings are wildcards (the
    common fully-delegated case) and never flag.

SPMD note: on single-device meshes (this repo's CI) ``jit``-level
``NamedSharding`` constraints do not appear as jaxpr collectives — the
partitioner inserts them post-lowering — so today's entries prove clean
trivially.  The value is the contract: the moment a ``shard_map``/
``pmap`` chunk fn lands (the roadmap's next step), its collectives are
in the traced jaxpr and audited against the declared mesh.
"""
from __future__ import annotations

from typing import Any

import jax

from repro.analysis.findings import Finding
from repro.analysis.jaxpr_audit import EntryPoint, is_jit_call, iter_eqns

__all__ = ["audit_entry_sharding", "COLLECTIVE_PRIMITIVES"]

#: Collective primitives by jaxpr name.
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "pmax", "pmin", "ppermute", "all_gather", "all_to_all",
    "reduce_scatter", "axis_index", "pbroadcast",
})
#: The gathering subset S002 polices inside loop bodies.
_GATHERING = frozenset({"all_gather", "all_to_all"})


def _axis_names(eqn) -> tuple[str, ...]:
    """Named axes a collective eqn binds (positional/int axes are vmap
    internals, not mesh axes — skipped)."""
    raw = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    return tuple(a for a in raw if isinstance(a, str))


def _is_unspecified(sharding) -> bool:
    return sharding is None or \
        type(sharding).__name__ == "UnspecifiedValue"


def _check_donated_shardings(eqn, entry, findings) -> None:
    donated = eqn.params.get("donated_invars")
    in_sh = eqn.params.get("in_shardings")
    out_sh = eqn.params.get("out_shardings")
    if not donated or in_sh is None or out_sh is None:
        return
    name = eqn.params.get("name", "jit")
    for i, d in enumerate(donated):
        if not d or i >= len(in_sh) or i >= len(out_sh):
            continue
        s_in, s_out = in_sh[i], out_sh[i]
        if _is_unspecified(s_in) or _is_unspecified(s_out):
            continue
        if s_in != s_out:
            findings.append(Finding(
                "sharding", "S003", entry.name,
                f"jit {name!r}: donated carry leaf {i} has input "
                f"sharding {s_in} but output sharding {s_out} — the "
                "donation degrades to a copy; make the carry sharding "
                "a fixed point",
                detail=f"{name}:{i}"))


def audit_entry_sharding(entry: EntryPoint, closed: Any | None = None
                         ) -> tuple[list[Finding], dict]:
    """S001/S002/S003 over one audited entry point's jaxpr."""
    if closed is None:
        fn, args = entry.build()
        closed = jax.make_jaxpr(fn)(*args)
    declared = tuple(getattr(entry, "mesh_axes", None) or ())
    allowed_loop = tuple(getattr(entry, "allow_loop_collectives", None)
                         or ("psum",))
    findings: list[Finding] = []
    audited = 0
    for eqn, in_loop in iter_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        if is_jit_call(eqn):
            _check_donated_shardings(eqn, entry, findings)
        if prim not in COLLECTIVE_PRIMITIVES:
            continue
        audited += 1
        for axis in _axis_names(eqn):
            if axis not in declared:
                have = f"declared mesh axes {declared}" if declared \
                    else "no declared mesh axes (single-host contract)"
                findings.append(Finding(
                    "sharding", "S001", entry.name,
                    f"collective {prim!r} binds axis {axis!r} but the "
                    f"entry has {have} — declare the axis in the "
                    "EntryPoint or drop the collective",
                    detail=f"{prim}:{axis}"))
        if in_loop and prim in _GATHERING \
                and prim not in allowed_loop:
            findings.append(Finding(
                "sharding", "S002", entry.name,
                f"gathering collective {prim!r} inside a scan/while body "
                "re-materializes its operand every step (implicit "
                "per-step resharding); hoist it out of the loop or opt "
                "in via allow_loop_collectives",
                detail=f"loop:{prim}"))
    metrics = {"collectives_audited": audited}
    return findings, metrics

"""RNG key-lineage auditor (R-pass): dataflow over PRNG keys in jaxprs.

PR 9 fixed a decode-prefill bug — the seed implementation reused the
unsplit sampling key across prefill steps and re-split it in the decode
loop, shifting the key stream by prompt length — by hand.  That bug (and
its whole family) is mechanically detectable from the traced jaxpr: PRNG
keys are ordinary values whose producers (``random_seed`` /
``random_split`` / ``random_fold_in`` / ``random_wrap``) and consumers
(``random_bits``) appear as primitives.  This pass walks every audited
entry point's closed jaxpr tracking *key tokens* from creation to
consumption, across pjit/custom-vjp call boundaries and through scan
carries, and flags:

  * ``R001`` — a key consumed by ≥ 2 random draws (key reuse: identical
    bits drawn twice, or a stream silently correlated).  Consumption of
    an outer key inside a scan body counts once per iteration, so a
    captured key drawn in a loop of length n counts n times.
  * ``R002`` — a key consumed inside a scan body *and* returned in the
    carry unchanged (which JAX hoists into a loop-invariant scan const):
    every iteration draws from the same key.  The fix
    is ``fold_in``/``split`` inside the body (the carried token must
    differ from the one consumed).
  * ``R003`` — entropy discarded: a ``random_split`` none of whose
    results is ever consumed while at least one is dropped outright
    (``rng, _ = split(key)`` advancing a stream nobody draws from), or a
    random draw whose outputs are all dead (the pre-PR-9 prefill pattern:
    sampling during prefill and discarding the sample still shifted the
    stream).

Token identity is value identity: ``random_wrap`` of the same raw
``uint32[2]`` var twice yields ONE token (that is how reuse of an
unsplit key manifests after tracing), while each ``split``/``fold_in``
result is a fresh token.  Branches of ``cond`` are walked like calls, so
a key consumed in two *exclusive* branches counts twice — a deliberate
over-approximation (waivable per entry with ``# audit: safe(R001@...)``).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax

from repro.analysis.findings import Finding
from repro.analysis.jaxpr_audit import EntryPoint

__all__ = ["audit_entry_rng", "analyze_rng", "KeyToken"]

#: Lineage-preserving primitives: output token == input token.
_ALIAS_PRIMS = frozenset({
    "random_unwrap", "squeeze", "reshape", "convert_element_type",
    "transpose", "copy", "device_put", "broadcast_in_dim",
})
#: Extraction of one sub-key from a split family's stacked array.
_EXTRACT_PRIMS = frozenset({"slice", "dynamic_slice", "gather"})

_CONSUME = "random_bits"


@dataclasses.dataclass
class KeyToken:
    """One distinct PRNG key value flowing through the jaxpr."""

    seq: int
    origin: str                       # "seed" | "arg" | "split[i]#f" | ...
    scan_depth: int = 0               # how many scan bodies enclosed creation
    consumed: int = 0                 # total draws (scan-weighted)
    dead_draws: int = 0               # draws whose outputs are all dead
    escaped: bool = False             # reaches the top-level outputs
    derived: bool = False             # split/fold_in applied to it
    family: "_Family | None" = None   # set on random_split result tokens
    parent: "_Family | None" = None


@dataclasses.dataclass
class _Family:
    """One ``random_split`` result: a stacked array of n fresh keys."""

    seq: int
    n_keys: int
    children: dict[int, KeyToken] = dataclasses.field(default_factory=dict)
    whole_used: bool = False          # the stacked array escaped whole


class _State:
    def __init__(self):
        self.tokens: list[KeyToken] = []
        self.families: list[_Family] = []
        self.findings: list[Finding] = []
        self.scan_lengths: list[int] = []   # stack of enclosing scan lengths

    def new_token(self, origin: str) -> KeyToken:
        tok = KeyToken(seq=len(self.tokens), origin=origin,
                       scan_depth=len(self.scan_lengths))
        self.tokens.append(tok)
        return tok

    def consume(self, tok: KeyToken, *, live: bool) -> None:
        # A draw inside scans the token was created OUTSIDE of repeats once
        # per iteration of each of those scans.
        mult = 1
        for length in self.scan_lengths[tok.scan_depth:]:
            mult *= max(1, length)
        tok.consumed += mult
        if not live:
            tok.dead_draws += mult


def _is_dropvar(v) -> bool:
    return isinstance(v, jax.core.DropVar)


def _liveness(jaxpr, live_outvars: set) -> list[bool]:
    """Per-eqn liveness via one backward pass.  ``live_outvars`` is the
    subset of ``jaxpr.outvars`` actually needed by the caller."""
    needed = {id(v) for v in jaxpr.outvars
              if not _is_dropvar(v) and id(v) in live_outvars}
    live = [False] * len(jaxpr.eqns)
    for i in range(len(jaxpr.eqns) - 1, -1, -1):
        eqn = jaxpr.eqns[i]
        if any(id(v) in needed for v in eqn.outvars if not _is_dropvar(v)):
            live[i] = True
            for v in eqn.invars:
                if hasattr(v, "aval"):       # skip Literals
                    needed.add(id(v))
    return live


def _sub_jaxpr(eqn):
    """The single body jaxpr of a call-like eqn whose invars map 1:1."""
    for key in ("jaxpr", "call_jaxpr"):
        p = eqn.params.get(key)
        if p is None:
            continue
        sub = p.jaxpr if hasattr(p, "jaxpr") else p
        if hasattr(sub, "eqns"):
            return sub
    return None


def _walk(jaxpr, env: dict, state: _State, *, jaxpr_live: bool,
          live_outvars: set | None = None) -> None:
    """Forward token propagation over one (sub-)jaxpr.

    ``env`` maps var id -> KeyToken for key-carrying values.  ``jaxpr_live``
    False means the whole body is dead (its draws are dead draws).
    """
    if live_outvars is None:
        live_outvars = {id(v) for v in jaxpr.outvars if not _is_dropvar(v)}
    live = _liveness(jaxpr, live_outvars) if jaxpr_live \
        else [False] * len(jaxpr.eqns)

    for i, eqn in enumerate(jaxpr.eqns):
        prim = eqn.primitive.name
        eqn_live = jaxpr_live and live[i]
        if prim == "pallas_call":
            continue

        if prim == "random_seed":
            env[id(eqn.outvars[0])] = state.new_token("seed")
        elif prim == "random_wrap":
            src = eqn.invars[0]
            tok = env.get(id(src))
            if tok is None:
                tok = state.new_token("arg")
                if hasattr(src, "aval"):
                    env[id(src)] = tok   # a second wrap of src reuses it
            env[id(eqn.outvars[0])] = tok
        elif prim == "random_fold_in":
            parent = env.get(id(eqn.invars[0]))
            if parent is not None:
                parent.derived = True
            env[id(eqn.outvars[0])] = state.new_token(
                f"fold_in#{parent.seq if parent else '?'}")
        elif prim == "random_split":
            parent = env.get(id(eqn.invars[0]))
            if parent is None:
                parent = state.new_token("arg")
                if hasattr(eqn.invars[0], "aval"):
                    env[id(eqn.invars[0])] = parent
            parent.derived = True
            shape = eqn.params.get("shape") or \
                getattr(eqn.outvars[0].aval, "shape", (2,))
            fam = _Family(seq=len(state.families), n_keys=int(shape[0]))
            state.families.append(fam)
            tok = state.new_token(f"split#{fam.seq}")
            tok.family = fam
            env[id(eqn.outvars[0])] = tok
        elif prim == _CONSUME:
            tok = env.get(id(eqn.invars[0]))
            if tok is None:
                tok = state.new_token("arg")
                if hasattr(eqn.invars[0], "aval"):
                    env[id(eqn.invars[0])] = tok
            state.consume(tok, live=eqn_live)
        elif prim in _EXTRACT_PRIMS:
            src_tok = env.get(id(eqn.invars[0]))
            if src_tok is None:
                pass
            elif src_tok.family is not None:
                fam = src_tok.family
                idx = None
                if prim == "slice":
                    idx = int(eqn.params["start_indices"][0])
                if idx is not None and idx in fam.children:
                    child = fam.children[idx]
                else:
                    child = state.new_token(
                        f"split[{idx if idx is not None else '?'}]"
                        f"#{fam.seq}")
                    child.parent = fam
                    fam.children[idx if idx is not None
                                 else -1 - len(fam.children)] = child
                if not _is_dropvar(eqn.outvars[0]):
                    env[id(eqn.outvars[0])] = child
            else:
                if not _is_dropvar(eqn.outvars[0]):
                    env[id(eqn.outvars[0])] = src_tok
        elif prim in _ALIAS_PRIMS:
            tok = env.get(id(eqn.invars[0]))
            if tok is not None and not _is_dropvar(eqn.outvars[0]):
                env[id(eqn.outvars[0])] = tok
        elif prim == "scan":
            _walk_scan(eqn, env, state, eqn_live)
        elif prim == "cond":
            branches = eqn.params.get("branches", ())
            for br in branches:
                sub = br.jaxpr if hasattr(br, "jaxpr") else br
                if len(sub.invars) != len(eqn.invars) - 1:
                    continue
                sub_env = dict(env)
                for outer, inner in zip(eqn.invars[1:], sub.invars):
                    tok = env.get(id(outer))
                    if tok is not None:
                        sub_env[id(inner)] = tok
                _walk(sub, sub_env, state, jaxpr_live=eqn_live)
        else:
            sub = _sub_jaxpr(eqn)
            if sub is not None and len(sub.invars) == len(eqn.invars):
                sub_env = dict(env)
                for outer, inner in zip(eqn.invars, sub.invars):
                    tok = env.get(id(outer))
                    if tok is not None:
                        sub_env[id(inner)] = tok
                sub_live = {id(v) for v in sub.outvars
                            if not _is_dropvar(v)} if eqn_live else set()
                _walk(sub, sub_env, state, jaxpr_live=eqn_live,
                      live_outvars=sub_live)
                for outer, inner in zip(eqn.outvars, sub.outvars):
                    tok = sub_env.get(id(inner))
                    if tok is not None and not _is_dropvar(outer):
                        env[id(outer)] = tok
            else:
                # Unknown structure (while, custom ops): recurse for
                # consumption counting with a fresh environment.
                for p in eqn.params.values():
                    for q in (p if isinstance(p, (tuple, list)) else (p,)):
                        body = q.jaxpr if hasattr(q, "jaxpr") else q
                        if hasattr(body, "eqns"):
                            _walk(body, {}, state, jaxpr_live=eqn_live)


def _walk_scan(eqn, env: dict, state: _State, eqn_live: bool) -> None:
    body = eqn.params["jaxpr"]
    sub = body.jaxpr if hasattr(body, "jaxpr") else body
    n_consts = eqn.params.get("num_consts", 0)
    n_carry = eqn.params.get("num_carry", 0)
    length = int(eqn.params.get("length") or 2)

    sub_env: dict = {}
    carry_in: list[KeyToken | None] = []
    for pos, (outer, inner) in enumerate(zip(eqn.invars, sub.invars)):
        tok = env.get(id(outer))
        if pos >= n_consts + n_carry:
            # xs input: each iteration sees a different slice -> a fresh
            # per-iteration token, not the stacked array's.
            tok = state.new_token(f"scan_xs@{pos}") if tok is not None \
                else None
        if tok is not None:
            sub_env[id(inner)] = tok
        if n_consts <= pos < n_consts + n_carry:
            carry_in.append(tok)

    const_in = [sub_env.get(id(inner)) for inner in sub.invars[:n_consts]]
    consumed_before = {id(t): t.consumed for t in state.tokens}
    state.scan_lengths.append(length)
    _walk(sub, sub_env, state, jaxpr_live=eqn_live)
    state.scan_lengths.pop()

    # R002, hoisted form: JAX turns a carry that the body returns unchanged
    # into a scan const, so the same bug also arrives as a const key that
    # the body consumes on every iteration.
    for pos, tok in enumerate(const_in):
        if tok is not None and \
                tok.consumed > consumed_before.get(id(tok), 0):
            state.findings.append(_finding(
                "R002", f"loop-invariant key {tok.origin} is drawn from "
                "inside the scan body — every iteration replays the same "
                "stream", detail=f"const{pos}:{tok.origin}"))

    # R002: a carry key consumed in the body and returned unchanged.
    for pos in range(n_carry):
        tok_in = carry_in[pos] if pos < len(carry_in) else None
        if tok_in is None:
            continue
        out_tok = sub_env.get(id(sub.outvars[pos]))
        drew = tok_in.consumed > consumed_before.get(id(tok_in), 0)
        if out_tok is tok_in and drew:
            state.findings.append(_finding(
                "R002", f"carry key {tok_in.origin} is drawn from inside "
                "the scan body and carried forward unsplit — every "
                "iteration replays the same stream",
                detail=f"carry{pos}:{tok_in.origin}"))
        # Carry-out token maps to the scan eqn's outvars for the caller.
        if out_tok is not None and pos < len(eqn.outvars) \
                and not _is_dropvar(eqn.outvars[pos]):
            env[id(eqn.outvars[pos])] = out_tok


_WHERE = [""]  # set by analyze_rng for _finding


def _finding(rule: str, message: str, *, detail: str) -> Finding:
    return Finding("rng", rule, _WHERE[0], message, detail=detail)


def analyze_rng(closed, *, where: str) -> tuple[list[Finding], dict]:
    """Run the R-pass over one closed jaxpr."""
    state = _State()
    _WHERE[0] = where
    env: dict = {}
    _walk(closed.jaxpr, env, state, jaxpr_live=True)

    # Escapes: tokens reaching the top-level outputs.
    for v in closed.jaxpr.outvars:
        tok = env.get(id(v))
        if tok is not None:
            tok.escaped = True
            if tok.family is not None:
                tok.family.whole_used = True

    findings = list(state.findings)
    for tok in state.tokens:
        if tok.consumed >= 2:
            findings.append(_finding(
                "R001", f"key {tok.origin} consumed by {tok.consumed} "
                "random draws — split or fold_in before each draw",
                detail=f"{tok.origin}:x{tok.consumed}"))
        if tok.dead_draws:
            findings.append(_finding(
                "R003", f"{tok.dead_draws} random draw(s) from key "
                f"{tok.origin} produce only dead values — the draw still "
                "shifts any shared stream (the pre-PR-9 prefill pattern)",
                detail=f"{tok.origin}:dead-draw"))
    for fam in state.families:
        if fam.whole_used:
            continue
        kids = list(fam.children.values())
        consumed = [k for k in kids if k.consumed > 0]
        used = [k for k in kids
                if k.consumed > 0 or k.escaped or k.derived]
        dropped = [k for k in kids if k not in used]
        if dropped and not consumed:
            findings.append(_finding(
                "R003", f"split#{fam.seq} results dropped without any "
                f"draw ({len(dropped)} of {len(kids)} extracted keys "
                "unused) — the split only discards entropy",
                detail=f"split#{fam.seq}:dropped"))
    metrics = {
        "keys_traced": len(state.tokens),
        "splits_traced": len(state.families),
        "draws": sum(t.consumed for t in state.tokens),
        "dead_draws": sum(t.dead_draws for t in state.tokens),
    }
    return findings, metrics


def audit_entry_rng(entry: EntryPoint, closed: Any | None = None
                    ) -> tuple[list[Finding], dict]:
    """Trace ``entry`` (or reuse a shared trace) and run the R-pass."""
    if closed is None:
        fn, args = entry.build()
        closed = jax.make_jaxpr(fn)(*args)
    return analyze_rng(closed, where=entry.name)

"""Tests for the semantic audit tier (R/W/D/S passes + waivers).

Every rule is exercised as a twin: a known-bad fixture the pass must
flag and a known-good twin it must not.  The R-pass twins include a
reconstruction of the pre-PR-9 decode-prefill bug (the unsplit sampling
key reused across prefill steps, re-split only in the decode loop) —
the bug family this tier exists to catch mechanically.
"""
from __future__ import annotations

import os
import textwrap
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.analysis import (
    EntryPoint,
    Finding,
    analyze_rng,
    apply_waivers,
    audit_entry_determinism,
    audit_entry_rng,
    audit_entry_sharding,
    audit_races,
    audit_seeded_modules,
    check_launch_races,
    check_layout,
    check_tile_list,
    scan_waivers,
    stale_waiver_findings,
)
from repro.analysis.sharding_audit import _check_donated_shardings
from repro.analysis.vmem_audit import Block, Launch
from repro.core.metabatch import layout_from_occupancy

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(findings):
    return sorted(f.rule for f in findings)


def _rng(fn, *args):
    closed = jax.make_jaxpr(fn)(*args)
    return analyze_rng(closed, where="fixture")


# ============================================================ R-pass (rng)
class TestRngAudit:
    def test_r001_key_reuse_flagged(self):
        def bad():
            key = jax.random.PRNGKey(0)
            return jax.random.normal(key, (2,)), \
                jax.random.uniform(key, (2,))

        findings, metrics = _rng(bad)
        assert "R001" in _rules(findings)
        assert metrics["draws"] == 2

    def test_r001_split_before_each_draw_clean(self):
        def good():
            key = jax.random.PRNGKey(0)
            k1, k2 = jax.random.split(key)
            return jax.random.normal(k1, (2,)), \
                jax.random.uniform(k2, (2,))

        findings, metrics = _rng(good)
        assert findings == []
        assert metrics["draws"] == 2

    def test_r002_unsplit_scan_carry_flagged(self):
        def bad(x):
            def body(k, xi):
                return k, xi + jax.random.normal(k, ())
            _, ys = jax.lax.scan(body, jax.random.PRNGKey(0), x)
            return ys

        findings, metrics = _rng(bad, jnp.arange(4.0))
        assert "R002" in _rules(findings)
        # the carried key is drawn once per iteration -> R001 too
        assert "R001" in _rules(findings)
        assert metrics["draws"] == 4          # scan-weighted consumption

    def test_r002_split_inside_body_clean(self):
        def good(x):
            def body(k, xi):
                k, sub = jax.random.split(k)
                return k, xi + jax.random.normal(sub, ())
            _, ys = jax.lax.scan(body, jax.random.PRNGKey(0), x)
            return ys

        findings, _ = _rng(good, jnp.arange(4.0))
        assert findings == []

    def test_r003_dropped_split_flagged(self):
        def bad():
            rng, _ = jax.random.split(jax.random.PRNGKey(0))
            return rng                        # sibling never drawn from

        findings, _ = _rng(bad)
        assert _rules(findings) == ["R003"]

    def test_r003_consumed_sibling_clean(self):
        def good():
            rng, sub = jax.random.split(jax.random.PRNGKey(0))
            return rng, jax.random.normal(sub, ())

        findings, _ = _rng(good)
        assert findings == []

    # -- the pre-PR-9 decode-prefill bug, reconstructed ------------------
    def test_prefill_key_reuse_canary(self):
        """The old generate shape: sample during prefill with the unsplit
        key (discarding the sample), then re-split in the decode loop.
        The R-pass must flag both the reuse and the discarded entropy."""
        def old_generate(emb):
            key = jax.random.PRNGKey(0)
            for t in range(emb.shape[0]):     # prefill: sample & discard
                _ = jax.random.categorical(key, emb[t])
            toks, logits = [], emb[-1]
            for _s in range(3):               # decode: split per step
                key, sub = jax.random.split(key)
                toks.append(jax.random.categorical(sub, logits))
            return jnp.stack(toks)

        findings, metrics = _rng(old_generate, jnp.zeros((2, 7)))
        rules = _rules(findings)
        assert "R001" in rules                # unsplit key drawn twice
        assert "R003" in rules                # draws discarded
        assert metrics["dead_draws"] == 2

    def test_fixed_prefill_clean(self):
        def new_generate(emb):
            key = jax.random.PRNGKey(0)       # prefill: cache only, no RNG
            toks, logits = [], emb[-1]
            for _s in range(3):
                key, sub = jax.random.split(key)
                toks.append(jax.random.categorical(sub, logits))
            return jnp.stack(toks)

        findings, metrics = _rng(new_generate, jnp.zeros((2, 7)))
        assert findings == []
        assert metrics["dead_draws"] == 0

    def test_registered_serve_entry_clean(self):
        """The real serve/decode.generate entry traces clean and actually
        exercises the pass (keys split per decode step)."""
        from repro.analysis.entrypoints import serve_decode_generate

        findings, metrics = audit_entry_rng(serve_decode_generate)
        assert findings == []
        assert metrics["splits_traced"] >= 3
        assert metrics["draws"] >= 3


# =========================================================== W-pass (race)
class TestRaceAudit:
    def _launch(self, accum_axes):
        out = Block("out", (8, 8), "out",
                    index_map=lambda i, j: (i, 0),
                    accum_axes=accum_axes)
        return Launch("k", "fwd", (4, 3), (out,))

    def test_w001_undeclared_revisit_flagged(self):
        findings = check_launch_races(self._launch(()), where="t")
        assert _rules(findings) == ["W001"]

    def test_w001_declared_accum_axis_clean(self):
        findings = check_launch_races(self._launch((1,)), where="t")
        assert findings == []

    def test_w002_duplicate_tile(self):
        findings = check_tile_list([0, 0, 1], [1, 1, 0], [1, 1, 1], 2,
                                   where="t", name="l")
        assert "W002" in _rules(findings)

    def test_w003_unsorted_major(self):
        findings = check_tile_list([1, 0], [0, 0], [1, 1], 2,
                                   where="t", name="l")
        assert "W003" in _rules(findings)

    def test_w004_unvisited_line(self):
        findings = check_tile_list([0, 0], [0, 1], [1, 1], 2,
                                   where="t", name="l")
        assert _rules(findings) == ["W004"]

    def test_w004_occupancy_mismatch(self):
        occ = np.array([[True, True], [False, True]])
        findings = check_tile_list([0, 1], [0, 1], [1, 1], 2,
                                   occ=occ, where="t", name="l")
        assert "W004" in _rules(findings)

    def test_sentinel_and_padding_clean(self):
        # line 1 empty -> (1, 0, valid=0) sentinel; tail pad repeats it.
        findings = check_tile_list([0, 1, 1], [0, 0, 0], [1, 0, 0], 2,
                                   where="t", name="l")
        assert findings == []

    def test_seeded_layout_clean_and_corrupted_duplicate_flagged(self):
        rng = np.random.default_rng(0)
        occ = rng.random((6, 6)) < 0.35
        layout = layout_from_occupancy(occ, 16, list_len=48)
        assert check_layout(layout, where="t") == []

        rows = np.array(layout.rows)
        cols = np.array(layout.cols)
        idx = np.nonzero(np.array(layout.valid))[0]
        rows[idx[1]], cols[idx[1]] = rows[idx[0]], cols[idx[0]]
        findings = check_tile_list(rows, cols, layout.valid, layout.nt,
                                   where="t", name="l")
        assert "W002" in _rules(findings)

    def test_full_pass_clean_on_repo(self):
        findings, metrics = audit_races()
        assert findings == []
        assert metrics["launches_checked"] > 0
        assert metrics["tiles_proven_race_free"] > 0

    def test_blocksparse_validate_kwarg(self):
        from repro.kernels.ops import graph_regularizer_blocksparse

        W = np.kron(np.eye(3), np.ones((2, 2))).astype(np.float32)
        occ = W.reshape(3, 2, 3, 2).any((1, 3))
        layout = layout_from_occupancy(occ, 2)
        logp = jnp.log(jnp.full((6, 4), 0.25))
        out = graph_regularizer_blocksparse(
            logp, jnp.asarray(W), 1e-3, 1e-4, layout=layout, validate=True)
        assert np.isfinite(float(out))

        arrs = [np.array(a) for a in layout.arrays()]
        idx = np.nonzero(arrs[2])[0]
        arrs[0][idx[1]], arrs[1][idx[1]] = arrs[0][idx[0]], arrs[1][idx[0]]
        with pytest.raises(ValueError, match="W002"):
            graph_regularizer_blocksparse(
                logp, jnp.asarray(W), 1e-3, 1e-4,
                layout=tuple(arrs), validate=True)


# ==================================================== D-pass (determinism)
class TestDeterminismAudit:
    def _segment_entry(self, **kw):
        x = jnp.ones((8,), jnp.float32)
        idx = jnp.zeros((8,), jnp.int32)

        def f(x, idx):
            return jax.ops.segment_sum(x, idx, num_segments=4)

        return EntryPoint("seg", lambda: (f, (x, idx)), **kw)

    def test_d001_unordered_float_scatter_flagged(self):
        findings, metrics = audit_entry_determinism(self._segment_entry())
        assert _rules(findings) == ["D001"]
        assert metrics["scatters_checked"] == 1

    def test_d001_opt_out_entry_clean(self):
        findings, _ = audit_entry_determinism(
            self._segment_entry(deterministic=False))
        assert findings == []

    def test_d001_unique_indices_clean(self):
        x = jnp.ones((4,), jnp.float32)

        def f(x):
            return jnp.zeros(4).at[jnp.arange(4)].add(
                x, unique_indices=True)

        entry = EntryPoint("uniq", lambda: (f, (x,)))
        findings, _ = audit_entry_determinism(entry)
        assert findings == []

    def test_d001_int_scatter_clean(self):
        x = jnp.ones((8,), jnp.int32)
        idx = jnp.zeros((8,), jnp.int32)

        def f(x, idx):
            return jax.ops.segment_sum(x, idx, num_segments=4)

        entry = EntryPoint("iseg", lambda: (f, (x, idx)))
        findings, _ = audit_entry_determinism(entry)
        assert findings == []

    def _host(self, tmp_path, source, used=None):
        (tmp_path / "m.py").write_text(textwrap.dedent(source))
        return audit_seeded_modules({"m": "m.py"}, root=str(tmp_path),
                                    used=used)

    def test_d002_set_iteration_flagged(self, tmp_path):
        findings, _ = self._host(tmp_path, """
            def plan(items):
                pool = set(items)
                out = []
                for x in pool:
                    out.append(x)
                return out
        """)
        assert _rules(findings) == ["D002"]

    def test_d002_sorted_iteration_clean(self, tmp_path):
        findings, _ = self._host(tmp_path, """
            def plan(items):
                pool = set(items)
                out = []
                for x in sorted(pool):
                    out.append(x)
                return out
        """)
        assert findings == []

    def test_d002_tiebreak_and_materialization(self, tmp_path):
        findings, _ = self._host(tmp_path, """
            def pick(items, deg):
                pool = set(items)
                seed = max(pool, key=lambda u: deg[u])
                order = list(pool)
                first = pool.pop()
                return seed, order, first
        """)
        assert _rules(findings) == ["D002", "D002", "D002"]

    def test_d003_global_entropy_flagged(self, tmp_path):
        findings, _ = self._host(tmp_path, """
            import random
            import time
            import numpy as np

            def noisy():
                np.random.seed(0)
                a = random.random()
                g = np.random.default_rng()
                h = np.random.default_rng(int(time.time()))
                return a, g, h
        """)
        assert _rules(findings) == ["D003", "D003", "D003", "D003"]

    def test_d003_seeded_generator_clean(self, tmp_path):
        findings, _ = self._host(tmp_path, """
            import numpy as np

            def quiet(seed):
                g = np.random.default_rng(seed)
                return g.random(4)
        """)
        assert findings == []

    def test_line_waiver_suppresses_and_is_recorded(self, tmp_path):
        used: set = set()
        findings, metrics = self._host(tmp_path, """
            def plan(items):
                pool = set(items)
                out = []
                # audit: safe(D002): int-set order is stable in CPython
                for x in pool:
                    out.append(x)
                return out
        """, used=used)
        assert findings == []
        assert metrics["suppressed"] == 1
        assert len(used) == 1

    def test_seeded_modules_clean_on_repo(self):
        used: set = set()
        findings, metrics = audit_seeded_modules(root=REPO_ROOT, used=used)
        assert findings == []
        assert metrics["seeded_modules_scanned"] == 5
        # partition.py carries two waived D002 sites with reasons on record
        assert metrics["suppressed"] >= 2
        assert used


# ====================================================== S-pass (sharding)
class TestShardingAudit:
    def setup_method(self):
        self.mesh = jax.make_mesh((1,), ("data",))
        self.x = jnp.ones((4,), jnp.float32)

    def _psum_fn(self):
        def f(x):
            return jax.shard_map(lambda a: jax.lax.psum(a, "data"),
                                 mesh=self.mesh, in_specs=P("data"),
                                 out_specs=P(), check_vma=False)(x)
        return f

    def test_s001_undeclared_axis_flagged(self):
        entry = EntryPoint("sh", lambda: (self._psum_fn(), (self.x,)))
        findings, metrics = audit_entry_sharding(entry)
        assert _rules(findings) == ["S001"]
        assert metrics["collectives_audited"] == 1

    def test_s001_declared_axis_clean(self):
        entry = EntryPoint("sh", lambda: (self._psum_fn(), (self.x,)),
                           mesh_axes=("data",))
        findings, _ = audit_entry_sharding(entry)
        assert findings == []

    def _gather_in_scan_fn(self):
        def body_fn(x):
            def body(c, s):
                return c + jax.lax.all_gather(s, "data").sum(), 0.0
            out, _ = jax.lax.scan(body, 0.0, x)
            return out

        def f(x):
            return jax.shard_map(body_fn, mesh=self.mesh,
                                 in_specs=P("data"), out_specs=P(),
                                 check_vma=False)(x)
        return f

    def test_s002_gather_in_loop_flagged(self):
        entry = EntryPoint("sh", lambda: (self._gather_in_scan_fn(),
                                          (self.x,)),
                           mesh_axes=("data",))
        findings, _ = audit_entry_sharding(entry)
        assert _rules(findings) == ["S002"]

    def test_s002_opt_in_clean(self):
        entry = EntryPoint("sh", lambda: (self._gather_in_scan_fn(),
                                          (self.x,)),
                           mesh_axes=("data",),
                           allow_loop_collectives=("psum", "all_gather"))
        findings, _ = audit_entry_sharding(entry)
        assert findings == []

    def test_s003_donation_sharding_mismatch(self):
        sharded = NamedSharding(self.mesh, P("data"))
        replicated = NamedSharding(self.mesh, P())
        entry = SimpleNamespace(name="e")

        findings: list = []
        _check_donated_shardings(SimpleNamespace(params={
            "donated_invars": (True,), "in_shardings": (sharded,),
            "out_shardings": (replicated,), "name": "chunk"}),
            entry, findings)
        assert _rules(findings) == ["S003"]

        for out_sh in (sharded, None):   # fixpoint / wildcard: clean
            clean: list = []
            _check_donated_shardings(SimpleNamespace(params={
                "donated_invars": (True,), "in_shardings": (sharded,),
                "out_shardings": (out_sh,), "name": "chunk"}),
                entry, clean)
            assert clean == []


# ================================================= waivers / A001 / CLI
class TestWaivers:
    def test_scoped_waiver_matches_where_glob(self, tmp_path):
        src = "# audit: safe(R001@engine_*): replay is intentional here\n"
        path = tmp_path / "w.py"
        path.write_text(src)
        waivers = scan_waivers(str(path), relpath="w.py")
        assert len(waivers) == 1 and waivers[0].scope == "engine_*"

        hit = Finding("rng", "R001", "engine_capture", "m")
        miss = Finding("rng", "R001", "serve_decode_generate", "m")
        used: set = set()
        kept = apply_waivers([hit, miss], waivers, used=used)
        assert kept == [miss]
        assert used == {waivers[0].key}

    def test_stale_waiver_becomes_a001(self, tmp_path):
        path = tmp_path / "w.py"
        path.write_text("# audit: safe(D002): no longer needed\n")
        waivers = scan_waivers(str(path), relpath="w.py")

        stale = stale_waiver_findings(waivers, set(), ("determinism",))
        assert _rules(stale) == ["A001"]
        # not stale if its pass family did not run, or if it was used
        assert stale_waiver_findings(waivers, set(), ("vmem",)) == []
        assert stale_waiver_findings(
            waivers, {waivers[0].key}, ("determinism",)) == []


def test_cli_only_alias_and_github_format(tmp_path, monkeypatch, capsys):
    from repro.analysis import cli

    bad = Finding("vmem", "V001", "tuning[0]:rbf", "footprint too big",
                  line=7, path="src/repro/kernels/tuning.py")

    def fake_vmem(report):
        report.extend("vmem", [bad], {"rows_checked": 1})

    monkeypatch.setattr(cli, "_run_vmem", fake_vmem)
    args = ["--only", "vmem", "--format", "github",
            "--report", str(tmp_path / "report.json"),
            "--baseline", str(tmp_path / "baseline.json")]
    assert cli.main(args) == 1
    out = capsys.readouterr().out
    assert ("::error file=src/repro/kernels/tuning.py,line=7::"
            "[V001] tuning[0]:rbf: footprint too big") in out


def test_cli_race_pass_clean_on_repo(tmp_path):
    from repro.analysis import cli

    assert cli.main(["--only", "race",
                     "--report", str(tmp_path / "report.json"),
                     "--baseline", str(tmp_path / "baseline.json")]) == 0


def test_cli_rejects_unknown_pass():
    from repro.analysis import cli

    with pytest.raises(SystemExit):
        cli.main(["--only", "nonsense"])

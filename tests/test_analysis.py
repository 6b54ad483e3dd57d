"""Tests for the static audit toolkit (repro.analysis).

Each pass is exercised against a corpus of known-bad snippets it must
flag and known-good twins it must not — the analyzers are themselves
code under test, not just the code they audit.
"""
from __future__ import annotations

import json
import textwrap

import jax
import jax.numpy as jnp

from repro.analysis import (
    AuditReport,
    EntryPoint,
    Finding,
    VMEM_BUDGET_BYTES,
    audit_entry,
    audit_file,
    audit_paths,
    check_launch,
    check_tiles,
    count_bxb_intermediates,
    load_baseline,
    save_baseline,
    unbaselined,
    validate_tuning_table,
    vmem_footprint_bytes,
)
from repro.analysis.concurrency_audit import DEFAULT_TARGETS
from repro.analysis.vmem_audit import Block, Launch
from repro.kernels.tuning import DEFAULT_TILE_TABLE, TileSpec


def _rules(findings):
    return sorted(f.rule for f in findings)


# =========================================================== jaxpr auditor
class TestJaxprAudit:
    B = 64

    def _logp_W(self):
        logp = jax.nn.log_softmax(jnp.zeros((self.B, 39), jnp.float32), -1)
        return logp, jnp.ones((self.B, self.B), jnp.float32)

    def test_bad_dense_bxb_flagged(self):
        logp, W = self._logp_W()

        def f(logp, W):
            p = jnp.exp(logp)
            return -jnp.sum(W * (p @ logp.T))     # dense B×B product

        entry = EntryPoint("bad", lambda: (f, (logp, W)),
                           B=self.B, expect_bxb=0)
        findings, metrics = audit_entry(entry)
        assert "J002" in _rules(findings)
        assert metrics["bxb_outside_kernels"] >= 1

    def test_good_fused_twin_clean(self):
        from repro.kernels.ops import graph_regularizer_fused

        logp, W = self._logp_W()

        def f(logp, W):
            return graph_regularizer_fused(logp, W, 0.5, 1e-3)

        entry = EntryPoint("good", lambda: (jax.grad(f), (logp, W)),
                           B=self.B, expect_bxb=0)
        findings, metrics = audit_entry(entry)
        assert findings == []
        assert metrics["bxb_outside_kernels"] == 0

    def test_canary_guards_the_counter(self):
        logp, W = self._logp_W()
        entry = EntryPoint("canary", lambda: ((lambda lp, w: lp.sum()),
                                              (logp, W)),
                           B=self.B, expect_bxb=None, canary_min_bxb=3)
        findings, _ = audit_entry(entry)
        assert _rules(findings) == ["J000"]

    def test_bf16_promotion_flagged_and_twin_clean(self):
        x = jnp.zeros((64, 64), jnp.bfloat16)

        def bad(x):
            return x.astype(jnp.float32) @ x.astype(jnp.float32).T

        bad_f, _ = audit_entry(EntryPoint(
            "promo", lambda: (bad, (x,)), compute_dtype="bfloat16"))
        assert "J003" in _rules(bad_f)
        good_f, _ = audit_entry(EntryPoint(
            "promo_ok", lambda: ((lambda x: x * jnp.bfloat16(2)), (x,)),
            compute_dtype="bfloat16"))
        assert good_f == []

    def test_f64_leak_flagged(self):
        x = jnp.zeros((8, 8), jnp.float32)
        with jax.enable_x64(True):
            findings, _ = audit_entry(EntryPoint(
                "leak", lambda: ((lambda x: x.astype(jnp.float64) * 2.0),
                                 (x,))))
        assert "J003" in _rules(findings)

    def test_callback_inside_scan_flagged(self):
        def bad(x):
            def body(c, _):
                jax.debug.print("step {}", c)
                return c + 1, None
            return jax.lax.scan(body, x, None, length=4)[0]

        def good(x):
            jax.debug.print("before the loop {}", x)   # outside the scan
            def body(c, _):
                return c + 1, None
            return jax.lax.scan(body, x, None, length=4)[0]

        x = jnp.float32(0)
        bad_f, _ = audit_entry(EntryPoint("cb", lambda: (bad, (x,))))
        assert "J004" in _rules(bad_f)
        good_f, _ = audit_entry(EntryPoint("cb_ok", lambda: (good, (x,))))
        assert good_f == []

    def test_captured_constant_flagged(self):
        big = jnp.ones((512, 512), jnp.float32)       # 1 MiB closure const

        findings, metrics = audit_entry(EntryPoint(
            "const", lambda: ((lambda x: x @ big),
                              (jnp.zeros((4, 512)),))))
        assert "J006" in _rules(findings)
        assert metrics["captured_const_bytes"] >= big.nbytes

    def test_donation_check(self):
        def inner(c, b):
            return c + b

        jitted = jax.jit(inner)                       # no donate_argnums
        donated = jax.jit(inner, donate_argnums=0)
        args = (jnp.zeros((4,)), jnp.ones((4,)))

        bad_f, bad_m = audit_entry(EntryPoint(
            "nodonate", lambda: ((lambda c, b: jitted(c, b)), args),
            donate=("inner", None)))
        assert "J005" in _rules(bad_f) and bad_m["carry_donated"] is False

        good_f, good_m = audit_entry(EntryPoint(
            "donate", lambda: ((lambda c, b: donated(c, b)), args),
            donate=("inner", None)))
        assert good_f == [] and good_m["carry_donated"] is True

    def test_registered_entry_points_hold_their_contracts(self):
        from repro.api.registry import AUDIT

        by_name = {}
        for name in AUDIT:
            findings, metrics = audit_entry(AUDIT.get(name))
            assert findings == [], (name, [f.format() for f in findings])
            by_name[name] = metrics
        # The acceptance numbers: fused Eq.-3/4 fwd+bwd at 0 dense B×B,
        # the jnp reference at >= 3, every engine carry donated.
        assert by_name["graph_reg_fused"]["bxb_outside_kernels"] == 0
        assert by_name["graph_reg_ref"]["bxb_outside_kernels"] >= 3
        assert by_name["ssl_objective"]["bxb_outside_kernels"] == 0
        assert by_name["knn_topk"]["bxb_outside_kernels"] == 0
        for strat in ("sequential", "sync_mesh", "async_ps"):
            assert by_name[f"engine_{strat}"]["carry_donated"] is True

    def test_counter_reexported_from_benchmarks(self):
        from benchmarks.bench_kernels import (
            count_bxb_intermediates as bench_counter)

        assert bench_counter is count_bxb_intermediates


# ===================================================== VMEM/tiling checker
class TestVmemAudit:
    def test_default_tuning_table_validates_clean(self):
        findings, metrics = validate_tuning_table()
        assert findings == [], [f.format() for f in findings]
        assert metrics["rows_checked"] == len(DEFAULT_TILE_TABLE)
        for kernel, worst in metrics["worst_footprint_bytes"].items():
            assert worst <= VMEM_BUDGET_BYTES, kernel

    def test_oversubscribed_tiles_flagged_and_twin_clean(self):
        bad = check_tiles("graph_reg", TileSpec(bi=1024, bj=1024, bc=2048),
                          where="corpus")
        assert "V001" in _rules(bad)
        assert vmem_footprint_bytes(
            "graph_reg", TileSpec(bi=1024, bj=1024, bc=2048)) \
            > VMEM_BUDGET_BYTES
        good = check_tiles("graph_reg", TileSpec(bi=128, bj=128, bc=512),
                           where="corpus")
        assert good == []

    def test_unaligned_tiles_flagged_on_tpu_rows_only(self):
        bad = check_tiles("rbf", TileSpec(bi=100, bj=130, bd=256),
                          where="corpus")
        assert set(_rules(bad)) == {"V002"}
        # An explicitly non-TPU row skips the lane/sublane rule.
        cpu = check_tiles("rbf", TileSpec(bi=100, bj=130, bd=256),
                          where="corpus", backend="cpu")
        assert "V002" not in _rules(cpu)

    def test_shadowed_row_and_missing_model(self):
        table = [
            ("graph_reg", None, None, TileSpec(bi=128, bj=128, bc=512)),
            ("graph_reg", "tpu", 512, TileSpec(bi=128, bj=128, bc=256)),
            ("mystery", None, None, TileSpec()),
        ]
        findings, _ = validate_tuning_table(table)
        assert _rules(findings) == ["V004", "V005"]

    def test_out_of_bounds_index_map_flagged(self):
        launch = Launch("demo", "fwd", (4, 2), (
            Block("x", (128, 128), "in",
                  index_map=lambda i, j: (i + 1, j),
                  array_shape=(512, 256)),
        ))
        findings = check_launch(launch, where="corpus")
        assert "V003" in _rules(findings)
        ok = Launch("demo", "fwd", (4, 2), (
            Block("x", (128, 128), "in", index_map=lambda i, j: (i, j),
                  array_shape=(512, 256)),
        ))
        assert check_launch(ok, where="corpus") == []

    def test_footprint_double_buffers_io_but_not_scratch(self):
        launch = Launch("demo", "fwd", (1,), (
            Block("in", (128, 128), "in"),
            Block("out", (128, 128), "out"),
            Block("scratch", (128, 128), "scratch"),
        ))
        tile = 128 * 128 * 4
        assert launch.footprint_bytes() == 2 * tile + 2 * tile + tile


# ======================================================== concurrency lint
def _lint(tmp_path, source):
    path = tmp_path / "snippet.py"
    path.write_text(textwrap.dedent(source))
    findings, _ = audit_file(str(path), where="snippet")
    return findings


class TestConcurrencyAudit:
    def test_unlocked_guarded_attribute_flagged(self, tmp_path):
        findings = _lint(tmp_path, """
            import threading
            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0
                def bump(self):
                    with self._lock:
                        self.count += 1
                def peek(self):
                    return self.count
        """)
        assert _rules(findings) == ["C001"]
        assert findings[0].detail == "count@peek"

    def test_locked_twin_clean(self, tmp_path):
        findings = _lint(tmp_path, """
            import threading
            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0
                def bump(self):
                    with self._lock:
                        self.count += 1
                def peek(self):
                    with self._lock:
                        return self.count
        """)
        assert findings == []

    def test_nested_fn_under_lock_is_not_locked(self, tmp_path):
        # A thread target *defined* inside a with-lock runs later, without
        # the lock — its accesses must still be flagged.
        findings = _lint(tmp_path, """
            import threading
            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.state = 0
                def read(self):
                    with self._lock:
                        return self.state
                def sneaky(self):
                    with self._lock:
                        def later():
                            self.state += 1
                        return later
        """)
        assert _rules(findings) == ["C001"]
        assert findings[0].detail == "state@sneaky"

    def test_unjoined_nondaemon_thread_flagged(self, tmp_path):
        bad = _lint(tmp_path, """
            import threading
            def go():
                t = threading.Thread(target=print)
                t.start()
        """)
        assert _rules(bad) == ["C002"]
        good = _lint(tmp_path, """
            import threading
            def go():
                t = threading.Thread(target=print)
                t.start()
                t.join()
        """)
        assert good == []

    def test_publication_without_happens_before_flagged(self, tmp_path):
        bad = _lint(tmp_path, """
            import threading
            def go():
                box = {}
                def work():
                    box["x"] = 1
                t = threading.Thread(target=work, daemon=True)
                t.start()
                return box["x"]
        """)
        assert _rules(bad) == ["C003"]
        good = _lint(tmp_path, """
            import threading
            def go():
                box = {}
                def work():
                    box["x"] = 1
                t = threading.Thread(target=work, daemon=True)
                t.start()
                t.join()
                return box["x"]
        """)
        assert good == []

    def test_suppression_marker_waives_named_rule_only(self, tmp_path):
        src = """
            import threading
            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0
                def bump(self):
                    with self._lock:
                        self.count += 1
                def peek(self):
                    return self.count  # audit: safe({rule}): stats only
        """
        waived = _lint(tmp_path, src.format(rule="C001"))
        assert waived == []
        wrong_rule = _lint(tmp_path, src.format(rule="C002"))
        assert _rules(wrong_rule) == ["C001"]

    def test_repo_threaded_modules_are_clean(self):
        findings, metrics = audit_paths(DEFAULT_TARGETS, root=".")
        assert findings == [], [f.format() for f in findings]
        stream = metrics["files"]["src/repro/data/pipeline.py"]
        guarded = stream["classes"]["MetaBatchStream"]["guarded"]
        # The PR-5 handoff state is now lock-published.
        assert {"plan", "_pending", "_plan_epoch", "_failed"} <= set(guarded)


# ================================================ findings / baseline gate
class TestBaselineGate:
    def test_fingerprint_is_stable_across_lines(self):
        a = Finding("vmem", "V001", "tuning[0]:rbf", "msg", line=10)
        b = Finding("vmem", "V001", "tuning[0]:rbf", "other msg", line=99)
        assert a.fingerprint == b.fingerprint

    def test_baseline_roundtrip_and_gate(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        known = Finding("jaxpr", "J002", "x", "known")
        fresh = Finding("jaxpr", "J002", "y", "fresh")
        save_baseline(path, [known])
        baseline = load_baseline(path)
        assert unbaselined([known, fresh], baseline) == [fresh]
        assert load_baseline(str(tmp_path / "missing.json")) == set()

    def test_info_findings_do_not_gate(self):
        report = AuditReport()
        report.extend("vmem", [Finding("vmem", "V001", "x", "m",
                                       severity="info")])
        assert report.gating == []

    def test_report_serializes_new_findings(self, tmp_path):
        report = AuditReport()
        f = Finding("vmem", "V001", "x", "m")
        report.extend("vmem", [f], {"rows_checked": 1})
        path = str(tmp_path / "report.json")
        report.write(path, baseline=set())
        data = json.loads(open(path).read())
        assert data["new_findings"] == [f.fingerprint]
        assert data["metrics"]["vmem/rows_checked"] == 1


# ------------------------------------------------------------------- CLI
def test_cli_clean_run_exits_zero(tmp_path, capsys):
    from repro.analysis.cli import main

    report = str(tmp_path / "report.json")
    baseline = str(tmp_path / "baseline.json")
    assert main(["--passes", "vmem,concurrency", "--report", report,
                 "--baseline", baseline]) == 0
    data = json.loads(open(report).read())
    assert data["passes"]["vmem"]["findings"] == 0

def test_cli_gates_on_unbaselined_findings(tmp_path, monkeypatch):
    from repro.analysis import cli

    bad_finding = Finding("vmem", "V001", "corpus", "too big")

    def fake_vmem(report):
        report.extend("vmem", [bad_finding], {"rows_checked": 1})

    monkeypatch.setattr(cli, "_run_vmem", fake_vmem)
    report = str(tmp_path / "report.json")
    baseline = str(tmp_path / "baseline.json")
    args = ["--passes", "vmem", "--report", report, "--baseline", baseline]
    assert cli.main(args) == 1                      # new finding -> fail
    assert cli.main(args + ["--update-baseline"]) == 0
    assert cli.main(args) == 0                      # accepted -> pass

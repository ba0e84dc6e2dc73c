"""Op-benchmark CI gate (reference tools/check_op_benchmark_result.py +
ci_op_benchmark.sh): comparator semantics + a tiny end-to-end run."""
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from op_benchmark import check_result, coverage_report  # noqa: E402


class TestCheckResult:
    def _base(self, **ops):
        return {"platform": "cpu", "ops": ops}

    def test_regression_fails_gate(self):
        ok, lines = check_result(self._base(matmul=1.30),
                                 self._base(matmul=1.00), tolerance=0.15)
        assert not ok
        assert any("REGRESSION" in l for l in lines)

    def test_within_tolerance_passes(self):
        ok, lines = check_result(self._base(matmul=1.10),
                                 self._base(matmul=1.00), tolerance=0.15)
        assert ok and not any("REGRESSION" in l for l in lines)

    def test_improvement_reported_not_failed(self):
        ok, lines = check_result(self._base(matmul=0.50),
                                 self._base(matmul=1.00))
        assert ok
        assert any("improved" in l for l in lines)

    def test_missing_op_fails(self):
        ok, lines = check_result(self._base(), self._base(matmul=1.0))
        assert not ok
        assert any("MISSING" in l for l in lines)

    def test_new_op_reported(self):
        ok, lines = check_result(self._base(gelu=0.1), self._base())
        assert ok
        assert any("new" in l for l in lines)

    def test_platform_mismatch_skips(self):
        cur = {"platform": "tpu", "ops": {"matmul": 9.9}}
        ok, lines = check_result(cur, self._base(matmul=1.0))
        assert ok
        assert any("platform mismatch" in l for l in lines)


class TestCoverageReport:
    """The anti-vacuous-pass satellite: rows with no baseline entry
    pass the regression gate vacuously and must be reported loudly
    (the committed TPU baseline guards 8 of 44 cases)."""

    def _base(self, **ops):
        return {"platform": "tpu", "ops": ops}

    def test_unguarded_rows_listed(self):
        ok, unguarded, lines = coverage_report(
            {"matmul", "gelu", "softmax"}, self._base(matmul=1.0))
        assert ok                      # informational without --strict
        assert unguarded == ["gelu", "softmax"]
        assert any("guards 1 of 3" in l for l in lines)
        assert sum("UNGUARDED" in l for l in lines) == 2
        assert any("vacuously" in l for l in lines)

    def test_strict_fails_on_gaps(self):
        ok, unguarded, lines = coverage_report(
            {"matmul", "gelu"}, self._base(matmul=1.0), strict=True)
        assert not ok
        assert unguarded == ["gelu"]
        assert any("FAILING" in l for l in lines)

    def test_full_coverage_passes_strict(self):
        ok, unguarded, lines = coverage_report(
            {"matmul"}, self._base(matmul=1.0), strict=True)
        assert ok and unguarded == []
        assert any("guards 1 of 1" in l for l in lines)

    def test_coverage_ignores_platform(self):
        """Unlike the timing gate, coverage compares NAMES — a
        platform-mismatched check must still scream about rows nobody
        guards anywhere."""
        base = {"platform": "tpu", "ops": {"matmul": 1.0}}
        ok, unguarded, _ = coverage_report({"matmul", "gelu"}, base,
                                           strict=True)
        assert not ok and unguarded == ["gelu"]

    def test_run_with_crashed_case_exits_nonzero(self, monkeypatch,
                                                 capsys):
        """A crashed case no longer kills the sweep, but `run` must
        stay loud about it (rc 1), not regress to silent success."""
        import op_benchmark as ob

        monkeypatch.setattr(ob, "run_bench", lambda out=None: {
            "platform": "cpu", "ops": {"matmul": 1.0},
            "failed": {"gelu": "RuntimeError('boom')"}})
        assert ob.main(["run"]) == 1
        assert "FAILED" in capsys.readouterr().out
        monkeypatch.setattr(ob, "run_bench", lambda out=None: {
            "platform": "cpu", "ops": {"matmul": 1.0}})
        assert ob.main(["run"]) == 0

    def test_update_strict_refuses_partial_baseline(self, tmp_path,
                                                    monkeypatch,
                                                    capsys):
        """update --strict-coverage must gate BEFORE writing: a
        mid-sweep crash cannot replace the committed baseline with a
        narrowed one."""
        import op_benchmark as ob

        baseline = tmp_path / "base.json"
        baseline.write_text(json.dumps(
            {"platform": "cpu", "ops": {"matmul": 1.0, "gelu": 2.0}}))
        monkeypatch.setattr(ob, "run_bench", lambda out=None: {
            "platform": "cpu", "ops": {"matmul": 1.1},
            "failed": {"gelu": "RuntimeError('boom')"}})
        rc = ob.main(["update", "--baseline", str(baseline),
                      "--strict-coverage"])
        assert rc == 1
        assert "NOT written" in capsys.readouterr().out
        # committed baseline untouched
        assert json.loads(baseline.read_text())["ops"] == {
            "matmul": 1.0, "gelu": 2.0}
        # non-strict update refuses too: pre-resilient-sweep behavior
        # was crash-before-write, and a silently narrowed baseline is
        # the vacuous-pass failure mode this gate exists to close
        rc = ob.main(["update", "--baseline", str(baseline)])
        assert rc == 1
        assert json.loads(baseline.read_text())["ops"] == {
            "matmul": 1.0, "gelu": 2.0}
        # without a crash the refresh goes through
        monkeypatch.setattr(ob, "run_bench", lambda out=None: {
            "platform": "cpu", "ops": {"matmul": 1.1, "gelu": 2.1}})
        rc = ob.main(["update", "--baseline", str(baseline),
                      "--strict-coverage"])
        assert rc == 0
        assert json.loads(baseline.read_text())["ops"] == {
            "matmul": 1.1, "gelu": 2.1}

    def test_committed_baseline_gap_is_visible(self):
        """The motivating case: the committed TPU baseline guards only
        the original 8 rows of the ~44-case sweep."""
        path = os.path.join(os.path.dirname(__file__), "..", "tools",
                            "op_bench_baseline.json")
        with open(path) as f:
            base = json.load(f)
        # stand-in for a full measured run: 44 case names
        measured = set(base["ops"]) | {"case_%d" % i for i in range(36)}
        ok, unguarded, lines = coverage_report(measured, base,
                                               strict=True)
        assert not ok
        assert len(unguarded) == 36
        assert any("guards %d of %d" % (len(base["ops"]), len(measured))
                   in l for l in lines)

"""Tests for repro.analysis: the lint engine, the four repo-specific
rules (via seeded fixture files), and the packed-artifact invariant
validator (via seeded corruption classes)."""
import dataclasses
import json
import pathlib

import numpy as np
import pytest

import repro.sparse_api as sp
from repro.analysis import analyze_file, analyze_paths
from repro.analysis.__main__ import main as analysis_main
from repro.analysis.validate import InvariantViolation, validate
from repro.core.hflex import pack_pe_streams
from repro.core.partition import SextansParams
from repro.core.schedule import (Schedule, min_dependency_distance,
                                 schedule_nonzeros)
from repro.core.sparse import power_law_sparse

HERE = pathlib.Path(__file__).parent
FIXTURES = HERE / "fixtures" / "analysis"
REPO = HERE.parent


def _marker_line(path: pathlib.Path) -> int:
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        if "# VIOLATION" in line:
            return i
    raise AssertionError(f"no # VIOLATION marker in {path}")


# ---------------------------------------------------------------------------
# Lint engine + rules


class TestRules:
    @pytest.mark.parametrize("fixture, rule", [
        ("viol_trace_hazard.py", "trace-hazard"),
        ("viol_host_device.py", "host-device-boundary"),
        ("viol_lock_discipline.py", "lock-discipline"),
        ("viol_donation.py", "donation-safety"),
    ])
    def test_rule_catches_seeded_fixture(self, fixture, rule):
        path = FIXTURES / fixture
        findings, suppressed = analyze_file(str(path))
        assert [f.rule for f in findings] == [rule]
        assert findings[0].line == _marker_line(path)
        assert suppressed == 0

    def test_suppressions_silence_all_four(self):
        findings, suppressed = analyze_file(str(FIXTURES / "clean_suppressed.py"))
        assert findings == []
        assert suppressed == 4

    def test_trace_hazard_allows_bucketing_helpers(self):
        src = ("def f(self, t, b):\n"
               "    exec_key = (t.geometry, cdiv(b.shape[1], 128) * 128)\n"
               "    return exec_key\n")
        findings, _ = analyze_file("mem.py", source=src)
        assert findings == []

    def test_trace_hazard_flags_key_returning_function(self):
        src = ("def group_key(t, b):\n"
               "    return (t.geometry, len(b))\n")
        findings, _ = analyze_file("mem.py", source=src)
        assert [f.rule for f in findings] == ["trace-hazard"]
        assert findings[0].line == 2

    def test_lock_discipline_honors_declared_guard_set(self):
        src = ("class C:\n"
               "    _lock_guarded = ('state',)\n"
               "    def touch(self):\n"
               "        self.state = 1\n")
        findings, _ = analyze_file("mem.py", source=src)
        assert [f.rule for f in findings] == ["lock-discipline"]
        assert findings[0].line == 4

    def test_donation_rebind_pattern_is_clean(self):
        src = ("def run(self, ops, acc):\n"
               "    for _ in range(3):\n"
               "        acc = self._step_exec(*ops, acc)\n"
               "    return acc\n")
        findings, _ = analyze_file("mem.py", source=src)
        assert findings == []

    def test_syntax_error_is_a_finding(self):
        findings, _ = analyze_file("mem.py", source="def broken(:\n")
        assert [f.rule for f in findings] == ["syntax-error"]


class TestCli:
    def test_clean_tree_exits_zero(self, capsys):
        rc = analysis_main([str(REPO / "src"), str(REPO / "tests")])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "0 finding(s)" in out

    def test_fixture_dir_exits_nonzero(self, capsys):
        rc = analysis_main([str(FIXTURES)])
        assert rc == 1
        assert "[trace-hazard]" in capsys.readouterr().out

    def test_fixtures_are_pruned_from_recursive_walk(self):
        result = analyze_paths([str(HERE)])
        assert result["findings"] == []
        assert result["files_scanned"] > 0

    def test_json_report(self, capsys):
        rc = analysis_main([str(FIXTURES), "--json"])
        assert rc == 1
        payload = json.loads(capsys.readouterr().out)
        rules = {f["rule"] for f in payload["findings"]}
        assert rules == {"trace-hazard", "host-device-boundary",
                         "lock-discipline", "donation-safety"}
        assert payload["suppressed"] == 4
        assert payload["files_scanned"] == 5

    def test_list_rules(self, capsys):
        rc = analysis_main(["--list-rules"])
        assert rc == 0
        out = capsys.readouterr().out
        for rid in ("trace-hazard", "host-device-boundary",
                    "lock-discipline", "donation-safety"):
            assert rid in out


# ---------------------------------------------------------------------------
# Invariant validator


def _tensor(m=250, k=300, seed=0):
    return sp.from_sparse_matrix(power_law_sparse(m, k, 5, seed=seed),
                                 tm=64, k0=128, chunk=8, bucket=True)


def _corrupt(t, **payload_fields):
    return dataclasses.replace(
        t, data=dataclasses.replace(t.data, **payload_fields))


class TestValidator:
    def test_clean_artifacts_pass(self, rng):
        t = _tensor()
        validate(t)
        validate(t.data)
        validate(t.windows(0, 2))
        s = sp.stack_hflex([_tensor(seed=i) for i in range(3)])
        validate(s)
        dense = np.zeros((100, 90), np.float32)
        dense[:40, :30] = rng.standard_normal((40, 30))
        validate(sp.from_dense(dense, format=sp.Format.BSR, block=(32, 32)))
        a = power_law_sparse(256, 300, 5, seed=0)
        validate(pack_pe_streams(a, SextansParams(P=8, K0=128, D=5)))

    def test_rejects_out_of_window_cols(self):
        t = _tensor()
        cols = np.asarray(t.data.cols).copy()
        cols[0, 0, 0] = t.data.k0          # window-local bound is K0
        with pytest.raises(InvariantViolation, match="window-local"):
            validate(_corrupt(t, cols=cols))

    def test_rejects_nse_overflow(self):
        t = _tensor()
        nse = np.asarray(t.data.nse).copy()
        nse[0, 0] = np.asarray(t.data.q)[0, 0] + 3
        with pytest.raises(InvariantViolation, match="nse overflows q"):
            validate(_corrupt(t, nse=nse))

    def test_rejects_non_monotone_stream_q(self):
        a = power_law_sparse(256, 300, 5, seed=0)
        ps = pack_pe_streams(a, SextansParams(P=8, K0=128, D=5))
        q = [qq.copy() for qq in ps.q]
        q[0][1], q[0][2] = q[0][2] + 1, q[0][1]
        with pytest.raises(InvariantViolation, match="not monotone"):
            validate(dataclasses.replace(ps, q=q))

    def test_rejects_ii_distance_violation(self):
        rows = np.array([3, 3, 3, 3], np.int64)
        sched = Schedule(slots=np.arange(4, dtype=np.int64), cycles=4,
                         nnz=4, d=5)
        with pytest.raises(InvariantViolation, match="row 3"):
            validate(sched, rows=rows)
        legal = schedule_nonzeros(rows, 5)
        validate(legal, rows=rows)
        assert min_dependency_distance(legal, rows) >= 5

    def test_rejects_geometry_mismatched_group_member(self):
        s = sp.stack_hflex([_tensor(seed=i) for i in range(3)])
        # member 1's payload claims a row beyond the group's logical M
        rows = np.asarray(s.data.rows).copy()
        nse = np.asarray(s.data.nse)
        w = int(np.argmax(nse[1, -1] > 0))
        rows[1, -1, w, 0] = s.data.tm - 1
        with pytest.raises(InvariantViolation, match=r"\[1, 3,"):
            validate(_corrupt(s, rows=rows))
        # and a logical shape that disagrees with the payload statics
        bad_shape = dataclasses.replace(s, shape=(s.m + 64, s.k))
        with pytest.raises(InvariantViolation, match="logical shape"):
            validate(bad_shape)

    def test_rejects_nonzero_padding_slot(self):
        t = _tensor()
        vals = np.asarray(t.data.vals).copy()
        slot = int(np.asarray(t.data.nse)[0, 0])
        assert slot < t.data.lw
        t.data.flat_slabs(vals)[0, 0, slot] = 7.0    # a view of vals
        with pytest.raises(InvariantViolation, match="padding slot"):
            validate(_corrupt(t, vals=vals))

    def test_rejects_unceiled_q(self):
        t = _tensor()
        q = np.asarray(t.data.q).copy()
        q[0, 0] += 1
        with pytest.raises(InvariantViolation, match="chunk-ceiled"):
            validate(_corrupt(t, q=q))

    def test_min_dependency_distance_none_without_repeats(self):
        rows = np.arange(6, dtype=np.int64)
        sched = schedule_nonzeros(rows, 4)
        assert min_dependency_distance(sched, rows) is None


class TestHooks:
    def test_spmm_hook_rejects_corrupt_tensor(self, sextans_check, rng):
        t = _tensor()
        cols = np.asarray(t.data.cols).copy()
        cols[0, 0, 0] = t.data.k0
        bad = _corrupt(t, cols=cols)
        b = rng.standard_normal((t.k, 8)).astype(np.float32)
        with pytest.raises(InvariantViolation):
            sp.spmm(bad, b, backend="jnp")

    def test_hook_disabled_without_env(self, monkeypatch, rng):
        monkeypatch.delenv("SEXTANS_CHECK", raising=False)
        t = _tensor()
        cols = np.asarray(t.data.cols).copy()
        cols[0, 0, 0] = t.data.k0          # harmless under "jnp": masked pad
        bad = _corrupt(t, cols=cols)
        b = rng.standard_normal((t.k, 8)).astype(np.float32)
        sp.spmm(bad.with_values(np.zeros_like(np.asarray(bad.data.vals))),
                b, backend="jnp")          # does not raise

    def test_plan_hook_validates_at_plan_time(self, sextans_check):
        t = _tensor()
        nse = np.asarray(t.data.nse).copy()
        nse[0, 0] = np.asarray(t.data.q)[0, 0] + 1
        with pytest.raises(InvariantViolation):
            sp.plan(_corrupt(t, nse=nse), 8, backend="jnp")

    def test_hooks_skip_traced_payloads(self, sextans_check, rng):
        import jax
        import jax.numpy as jnp

        t = _tensor(m=128, k=256)
        b = jnp.asarray(rng.standard_normal((t.k, 4)), jnp.float32)

        def loss(vals):
            return sp.spmm(t.with_values(vals), b, backend="jnp").sum()

        g = jax.grad(loss)(t.data.vals)    # windows/spmm hooks see tracers
        assert np.asarray(g).shape == np.asarray(t.data.vals).shape

    def test_streaming_checked_end_to_end(self, sextans_check, rng):
        t = _tensor(m=128, k=512)
        b = rng.standard_normal((t.k, 8)).astype(np.float32)
        y = sp.spmm_streaming(t, b, window_chunk=2, backend="jnp")
        ref = sp.spmm(t, b, backend="jnp")
        assert np.array_equal(np.asarray(y), np.asarray(ref))

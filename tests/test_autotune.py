"""Autotuned execution geometry + persistent tuning/plan cache tests
(``repro.sparse_api.autotune``).

Contract under test:

* tuning keys bucket like the executable cache (HFlex): contents never
  enter the key, geometry is bucketed, streaming embeds a budget class;
* the TuningDB round-trips records through a schema-versioned JSON file
  (atomic writes, file lock, read-merge on store), shrugs off corrupt or
  schema-mismatched files, and merges across instances/processes;
* ``plan(..., autotune=)`` applies stored decisions ("cached") or
  measures + stores on a miss ("measure"), and every accepted candidate
  is **bit-identical** to the default resolution — the tuner may only
  re-route among result-identical implementations;
* a second process reuses compiled code through JAX's persistent
  compilation cache, placed by ``repro.compile_cache``;
* the engine/scheduler surface the story as counters: plan-cache
  hits/misses/evictions, tuned dispatches, TuningDB traffic, cold vs
  warm plan-build seconds.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import repro.sparse_api as sp
from repro.core.sparse import power_law_sparse
from repro.sparse_api import autotune as at


@pytest.fixture()
def tune_dir(tmp_path, monkeypatch):
    d = tmp_path / "tunedb"
    d.mkdir()
    monkeypatch.setenv("SEXTANS_TUNE_DIR", str(d))
    return str(d)


def _packed(m=200, k=320, seed=1, tm=64, k0=64):
    a = power_law_sparse(m, k, 5, seed=seed)
    return sp.from_sparse_matrix(a, tm=tm, k0=k0, chunk=8, bucket=True)


class TestTuneKey:
    def test_contents_excluded_geometry_bucketed(self):
        """Two matrices in the same geometry bucket share a tuning key —
        the HFlex property carried into the tuner."""
        k1 = at.tune_key(_packed(seed=1), 8)
        k2 = at.tune_key(_packed(seed=9), 8)
        assert k1 == k2

    def test_n_buckets_pow2(self):
        A = _packed()
        assert at.tune_key(A, 9) == at.tune_key(A, 16)
        assert at.tune_key(A, 8) != at.tune_key(A, 16)

    def test_stream_tier_and_budget_class(self):
        A = _packed()
        res = at.tune_key(A, 8)
        srm = at.tune_key(A, 8, stream=True)
        assert res != srm and "stream" in srm
        # budgets in the same pow2 class share a key; different classes don't
        b1 = at.tune_key(A, 8, stream=True, device_bytes=1 << 20)
        b2 = at.tune_key(A, 8, stream=True, device_bytes=(1 << 20) + 5000)
        b3 = at.tune_key(A, 8, stream=True, device_bytes=1 << 22)
        assert b1 == b2 != b3

    def test_group_and_dtype_enter_key(self):
        A = _packed()
        assert at.tune_key(A, 8) != at.tune_key(A, 8, group=4)
        assert at.tune_key(A, 8) != at.tune_key(A, 8, dtype=jnp.float64)

    def test_schema_prefix(self):
        assert at.tune_key(_packed(), 8).startswith(f"v{at.TUNE_SCHEMA}|")


class TestTuningDB:
    def test_roundtrip_and_persistence(self, tune_dir):
        db = at.TuningDB(tune_dir)
        rec = {"schema": at.TUNE_SCHEMA, "backend": "jnp", "us": 12.5}
        db.store("k1", rec)
        assert db.lookup("k1")["backend"] == "jnp"
        # a FRESH instance reads the same file
        db2 = at.TuningDB(tune_dir)
        assert db2.lookup("k1")["us"] == 12.5
        assert len(db2) == 1

    def test_cross_instance_merge(self, tune_dir):
        """store() read-merges under the file lock: two instances writing
        different keys both survive (last-writer-wins per key, not per
        file)."""
        db1 = at.TuningDB(tune_dir)
        db2 = at.TuningDB(tune_dir)
        db1.store("a", {"schema": at.TUNE_SCHEMA, "v": 1})
        db2.store("b", {"schema": at.TUNE_SCHEMA, "v": 2})
        db3 = at.TuningDB(tune_dir)
        assert db3.lookup("a") and db3.lookup("b")

    def test_corrupt_file_tolerated(self, tune_dir):
        db = at.TuningDB(tune_dir)
        db.store("k", {"schema": at.TUNE_SCHEMA, "v": 1})
        with open(db.file, "w") as f:
            f.write("{not json")
        fresh = at.TuningDB(tune_dir)
        assert fresh.lookup("k") is None          # degraded, not raised
        fresh.store("k2", {"schema": at.TUNE_SCHEMA, "v": 2})
        assert fresh.lookup("k2")

    def test_schema_mismatch_discarded(self, tune_dir):
        db = at.TuningDB(tune_dir)
        db.store("k", {"schema": at.TUNE_SCHEMA, "v": 1})
        with open(db.file) as f:
            payload = json.load(f)
        payload["schema"] = at.TUNE_SCHEMA + 999
        with open(db.file, "w") as f:
            json.dump(payload, f)
        assert at.TuningDB(tune_dir).lookup("k") is None

    def test_no_dir_is_memory_only(self, monkeypatch):
        monkeypatch.delenv("SEXTANS_TUNE_DIR", raising=False)
        db = at.TuningDB(None)
        db.store("k", {"schema": at.TUNE_SCHEMA, "v": 1})
        assert db.lookup("k")["v"] == 1
        assert db.file is None


class TestResolveMode:
    def test_modes(self, monkeypatch):
        assert at.resolve_mode("measure") == "measure"
        monkeypatch.delenv("SEXTANS_AUTOTUNE", raising=False)
        assert at.resolve_mode(None) == "off"
        monkeypatch.setenv("SEXTANS_AUTOTUNE", "cached")
        assert at.resolve_mode(None) == "cached"

    def test_bogus_mode_raises(self, tune_dir):
        with pytest.raises(ValueError):
            sp.plan(_packed(), 8, autotune="bogus")


class TestTunedPlans:
    def test_measure_then_cached_bit_identical(self, tune_dir):
        """measure-mode tunes + stores; cached-mode applies the record;
        both run bit-identically to the default resolution."""
        rng = np.random.default_rng(0)
        A = _packed()
        b = jnp.asarray(rng.standard_normal((A.shape[1], 8)), jnp.float32)
        y_ref = np.asarray(sp.plan(A, 8).run(b))

        s0 = dict(at.TUNE_STATS)
        P = sp.plan(A, 8, autotune="measure")
        assert P.tuned
        assert at.TUNE_STATS["db_misses"] > s0["db_misses"]
        assert at.TUNE_STATS["measured"] > s0["measured"]
        np.testing.assert_array_equal(np.asarray(P.run(b)), y_ref)

        sp.clear_plan_cache()
        s1 = dict(at.TUNE_STATS)
        P2 = sp.plan(A, 8, autotune="cached")
        assert P2.tuned
        assert at.TUNE_STATS["db_hits"] > s1["db_hits"]
        assert at.TUNE_STATS["measured"] == s1["measured"]  # no re-measure
        np.testing.assert_array_equal(np.asarray(P2.run(b)), y_ref)

    def test_cached_without_record_is_default(self, tune_dir):
        P = sp.plan(_packed(seed=17, m=250), 8, autotune="cached")
        assert not P.tuned                        # empty DB: heuristics

    def test_explicit_backend_not_overridden(self, tune_dir):
        """Tuning only touches knobs the caller left open."""
        P = sp.plan(_packed(), 8, backend="jnp", autotune="measure")
        assert P.backend == "jnp" and not P.tuned

    def test_streaming_tune_bit_identical_and_coarser(self, tune_dir):
        """Forced streaming with no budget: the heuristic takes
        window_chunk=1; the tuner may pick any feasible chunking but the
        result must stay bit-identical."""
        rng = np.random.default_rng(0)
        A = _packed(m=256, k=512, k0=64)
        b = rng.standard_normal((512, 8)).astype(np.float32)
        S_def = sp.plan(A, 8, backend="jnp", stream=True)
        assert S_def.window_chunk == 1            # the heuristic floor
        S_tun = sp.plan(A, 8, backend="jnp", stream=True, autotune="measure")
        assert S_tun.tuned
        assert S_tun.window_chunk >= 1
        np.testing.assert_array_equal(np.asarray(S_tun.run(b)),
                                      np.asarray(S_def.run(b)))

    def test_tune_plan_records_decision(self, tune_dir):
        A = _packed()
        # the two backends; the kernel at its 128-column and narrow lanes
        assert {(c.backend, c.tn) for c in at.enumerate_candidates(A, 8)} == {
            ("jnp", None), ("pallas", 128), ("pallas", 8)}
        res = at.tune_plan(A, 8, repeats=2, measure_top=2)
        assert res.record["schema"] == at.TUNE_SCHEMA
        assert res.record["backend"] in sp.list_backends() == ["jnp",
                                                               "pallas"]
        db = at.get_db()
        assert db.lookup(res.key)["backend"] == res.record["backend"]
        # the stored decision beat or matched the default measurement
        assert res.record["us"] <= res.record["default_us"] * 1.5


class TestCompileCache:
    """repro.compile_cache: JAX's persistent compilation cache, placed from
    outside (it replaced the repo's own pickled-executable store)."""

    @pytest.fixture()
    def restore_config(self):
        import jax
        from jax.experimental.compilation_cache import compilation_cache as cc

        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs")
        saved = {k: getattr(jax.config, k) for k in keys}
        yield
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()

    def test_env_dir_is_honoured_and_nothing_set(self, tmp_path,
                                                 monkeypatch,
                                                 restore_config):
        import jax
        from repro import compile_cache

        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_unset_uses_fixed_checkout_dir(self, monkeypatch,
                                           restore_config):
        import jax
        from repro import compile_cache

        monkeypatch.delenv(compile_cache.ENV, raising=False)
        path = compile_cache.enable()
        assert path == compile_cache.checkout_dir()
        assert jax.config.jax_compilation_cache_dir == path
        root = os.path.dirname(path)
        assert os.path.basename(path) == ".jax_cache"
        assert os.path.isfile(os.path.join(root, "pyproject.toml"))
        assert compile_cache.enable() == path      # the same every call

    def test_recompile_after_clear_caches_is_a_hit(self, tmp_path,
                                                   restore_config):
        import jax
        from jax.experimental.compilation_cache import compilation_cache as cc
        from repro import compile_cache

        compile_cache.enable()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cc.reset_cache()

        def f(x):
            return jnp.sin(x) * 3.0 + jnp.cos(x[::-1])

        x = jnp.arange(37, dtype=jnp.float32)
        y0 = np.asarray(jax.jit(f)(x))
        assert os.listdir(tmp_path), "nothing was written to the cache"
        hits0 = compile_cache.STATS["hits"]
        jax.clear_caches()
        y1 = np.asarray(jax.jit(f)(x))
        assert compile_cache.STATS["hits"] > hits0
        np.testing.assert_array_equal(y0, y1)


class TestEngineCounters:
    def test_plan_cache_hits_misses_and_build_split(self, tune_dir):
        from repro.core.engine import SextansEngine

        rng = np.random.default_rng(0)
        a = power_law_sparse(128, 160, 5, seed=3)
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="jnp")
        t = eng.pack(a)
        b = jnp.asarray(rng.standard_normal((160, 8)), jnp.float32)
        eng.spmm(t, b)
        eng.spmm(t, b)
        st = eng.stats_snapshot()
        assert st.plan_cache_misses == 1
        assert st.plan_cache_hits == 1
        assert st.plan_cache_hit_rate == 0.5
        assert st.plan_builds_cold + st.plan_builds_warm == 1
        assert st.plan_build_cold_s + st.plan_build_warm_s > 0

    def test_eviction_counter(self, tune_dir):
        from repro.core.engine import SextansEngine

        rng = np.random.default_rng(0)
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="jnp")
        eng.PLAN_CACHE_CAP = 2                    # instance override
        t = eng.pack(power_law_sparse(128, 160, 5, seed=3))
        for n in (8, 16, 24, 32):
            eng.spmm(t, jnp.asarray(
                rng.standard_normal((160, n)), jnp.float32))
        st = eng.stats_snapshot()
        assert st.plan_cache_evictions >= 2
        assert st.plan_cache_misses == 4

    def test_tuned_dispatches_and_db_traffic(self, tune_dir):
        from repro.core.engine import SextansEngine

        rng = np.random.default_rng(0)
        a = power_law_sparse(128, 160, 5, seed=3)
        b = jnp.asarray(rng.standard_normal((160, 8)), jnp.float32)
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="auto",
                            autotune="measure")
        t = eng.pack(a)
        y1 = eng.spmm(t, b)
        st = eng.stats_snapshot()
        assert st.tuned_dispatches == 1
        assert st.tune_db_misses == 1             # cold: measured + stored
        assert st.plan_builds_cold == 1
        # second engine, same DB: pure hit, warm-or-cold build but no
        # re-measure, same bits
        eng2 = SextansEngine(tm=64, k0=64, chunk=8, impl="auto",
                             autotune="measure")
        y2 = eng2.spmm(eng2.pack(a), b)
        st2 = eng2.stats_snapshot()
        assert st2.tune_db_hits == 1 and st2.tune_db_misses == 0
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))

    def test_engine_off_mode_never_touches_db(self, tune_dir):
        from repro.core.engine import SextansEngine

        rng = np.random.default_rng(0)
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="auto")
        t = eng.pack(power_law_sparse(128, 160, 5, seed=3))
        eng.spmm(t, jnp.asarray(rng.standard_normal((160, 8)), jnp.float32))
        st = eng.stats_snapshot()
        assert st.tuned_dispatches == 0
        assert st.tune_db_hits == 0 and st.tune_db_misses == 0


class TestSchedulerSurface:
    def test_last_flush_and_cumulative_keys(self, tune_dir):
        from repro.core.engine import SextansEngine
        from repro.launch.serve import SpmmRequest, SpmmScheduler

        rng = np.random.default_rng(0)
        eng = SextansEngine(tm=64, k0=64, chunk=8, impl="auto")
        sched = SpmmScheduler(eng, autotune="measure")
        assert eng.autotune == "measure"          # mode threaded through
        for i in range(3):
            sched.submit(SpmmRequest(
                a=power_law_sparse(128, 160, 5, seed=i),
                b=rng.standard_normal((160, 8)).astype(np.float32)))
        sched.flush()
        for key in ("tuned_dispatches", "tune_db_hits", "tune_db_misses",
                    "plan_build_cold_s", "plan_build_warm_s"):
            assert key in sched.stats, key
            assert key in sched.stats["last_flush"], key
        lf = sched.stats["last_flush"]
        assert lf["tuned_dispatches"] > 0
        assert lf["tune_db_hits"] + lf["tune_db_misses"] > 0

    def test_serve_pool_warm_run_all_hits(self, tune_dir):
        from repro.core.engine import SextansEngine
        from repro.launch.serve import SpmmRequest, serve_spmm_requests

        rng = np.random.default_rng(0)
        reqs = [SpmmRequest(
            a=power_law_sparse(128, 160, 5, seed=i),
            b=rng.standard_normal((160, 8)).astype(np.float32))
            for i in range(4)]

        def run():
            eng = SextansEngine(tm=64, k0=64, chunk=8, impl="auto",
                                autotune="measure")
            return serve_spmm_requests(reqs, eng)

        outs1, stats1 = run()
        outs2, stats2 = run()
        assert stats2["tune_db_hits"] > 0
        assert stats2["tune_db_misses"] == 0
        assert stats2["tuned_dispatches"] > 0
        assert "plan_cache_hits" in stats2 and "plan_cache_misses" in stats2
        for a, b in zip(outs1, outs2):
            np.testing.assert_array_equal(a, b)


class TestSkinnyThresholdTuning:
    def test_tune_and_apply(self, tune_dir):
        import repro.sparse_api.backends as _bk

        try:
            thr = at.tune_skinny_threshold(_packed(), widths=[1, 4],
                                           repeats=1, apply=True)
            assert thr >= 0
            assert sp.skinny_n_max() == thr
            rec = at.get_db().lookup(at.skinny_key())
            assert rec["skinny_n_max"] == thr
        finally:
            _bk.set_skinny_n_max(None)

    def test_apply_from_db_respects_env(self, tune_dir, monkeypatch):
        import repro.sparse_api.backends as _bk

        db = at.get_db()
        db.store(at.skinny_key(), {"schema": at.TUNE_SCHEMA,
                                   "skinny_n_max": 3})
        monkeypatch.setenv("SEXTANS_SKINNY_N_MAX", "12")
        try:
            assert at.apply_skinny_from_db(db) is None   # env wins
            assert sp.skinny_n_max() == 12
        finally:
            _bk.set_skinny_n_max(None)


class TestCompareSnapshots:
    def test_regression_detection(self, tmp_path):
        run = pytest.importorskip(
            "benchmarks.run",
            reason="benchmarks package importable from repo root only")
        old = {"schema": 1, "rows": [
            {"name": "a", "us": 100.0, "derived": ""},
            {"name": "b", "us": 100.0, "derived": ""},
            {"name": "gone", "us": 1.0, "derived": ""}]}
        new = {"schema": 1, "rows": [
            {"name": "a", "us": 110.0, "derived": ""},     # within tolerance
            {"name": "b", "us": 200.0, "derived": ""},     # regression
            {"name": "added", "us": 1.0, "derived": ""}]}
        po, pn = tmp_path / "old.json", tmp_path / "new.json"
        po.write_text(json.dumps(old))
        pn.write_text(json.dumps(new))
        assert run.compare_snapshots(str(po), str(pn), tolerance=1.25) == 1
        assert run.compare_snapshots(str(po), str(pn), tolerance=3.0) == 0

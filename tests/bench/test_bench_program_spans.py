"""The program's own spans (``sextans.``) as the benchmark sees them: the
two set-up readers (``pack_s.setup``, ``plan_build_s.setup``) on the
program's span totals, and a traced run of each cell at a tiny size on the
CPU, whose trace holds the program's spans."""

import json
import os
import sys

import pytest

from bench_helpers import REPO, make_tiny_root, run_cell

from bench import run as R
from bench import trace

SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]
SETUP_READERS = {"pack_s.setup": "sextans.pack",
                 "plan_build_s.setup": "sextans.plan.build"}


def _reader(name):
    return R.load_module(os.path.join(REPO, "bench", "metrics", name + ".py"),
                         "test_reader_" + name.replace(".", "_"))


@pytest.mark.parametrize("name", sorted(SETUP_READERS))
def test_setup_reader_counts_only_the_spans_since_it_was_loaded(name):
    from repro.tracing import span

    with span(SETUP_READERS[name]):      # before the run: not counted
        pass
    reader = _reader(name)
    assert reader.read({}) is None       # no span of its own yet
    with span(SETUP_READERS[name]) as a:
        pass
    with span(SETUP_READERS[name]) as b:
        pass
    assert reader.read({}) == pytest.approx(a.wall_s + b.wall_s)


@pytest.mark.parametrize("name", sorted(SETUP_READERS))
def test_setup_reader_is_silent_without_the_recorder(name, monkeypatch):
    # a program from before ``repro.tracing``: the import fails
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert _reader(name).read({}) is None


def _program_spans(path):
    """The ``sextans.`` host events of the trace at ``path`` as ``[name,
    start_ns, duration_ns]``."""
    from jax.profiler import ProfileData

    return [[e.name, int(e.start_ns), int(e.duration_ns)]
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for ln in p.lines
            for e in ln.events if e.name.startswith("sextans.")]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reads_the_program_spans(cell, tmp_path, monkeypatch):
    loaded, spans = [], []
    load = trace.load

    def keep(path):
        spans.extend(_program_spans(path))
        loaded.append(load(path))
        return loaded[-1]

    monkeypatch.setattr(trace, "load", keep)
    res = run_cell(make_tiny_root(tmp_path), cell, trace=1)
    assert res["correct"], res["check"]
    m = res["metrics"]
    assert m["plan_build_s.setup"]["value"] > 0
    assert m["pack_s.setup"]["value"] > 0
    (t,) = loaded
    t0, t1 = trace.window(t)
    inside = [n for n, s, d in spans if t0 <= s and s + d <= t1]
    assert inside.count("sextans.plan.run") >= res["attempted"]
    if cell.startswith("olmo-ffn"):
        assert "sextans.layer.group" in inside
        assert "sextans.layer.stack_values" in inside
        assert "sextans.layer.linear" in inside
    else:
        assert "sextans.engine.spmm" in inside
    # packs and plan builds are set-up work, so the set-up readers, which
    # count the whole run, read set-up alone
    assert "sextans.pack" not in inside
    assert "sextans.plan.build" not in inside

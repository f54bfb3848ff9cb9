"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names a configuration and a traffic
mix.  The harness reads ``bench/configs/<config>.json`` and
``bench/traffic/<traffic>.json``; the traffic file's ``generator`` names the
generator in ``bench/generators/<generator>.py`` that builds the cell from the
seed, warms every shape it will use, drives the measured window and checks
the window's results against the plain reference.  Each per-layer metric
of the cell is read by ``bench/metrics/<metric>.py``.  Adding a cell, a
configuration, a traffic mix or a metric therefore takes new files only.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` profiles
the window and prints its per-layer metrics, the device's busy and window
seconds, and a ``breakdown`` of device ops and idle gaps.  The last line
of standard output is one JSON object; the numbers the correctness check
compared, each beside its limit, are the last lines of standard error and
the result's last key, ``check``.  Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(root: str, workload: str) -> Dict:
    """Everything the cell ``workload`` is made of, found by name."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    reports = lambda m: workload in m.get("workloads", [workload])  # noqa
    e2e = [m for m in spec["end_to_end"] if reports(m)]
    per_layer = [m for m in spec["per_layer"] if reports(m)]
    generator = load_module(os.path.join(root, "bench", "generators",
                                      traffic["generator"] + ".py"),
                         "bench_generator_" + traffic["generator"])
    readers = {m["name"]: load_module(
        os.path.join(root, "bench", "metrics", m["name"] + ".py"),
        "bench_metric_" + m["name"].replace(".", "_")) for m in per_layer}
    return dict(cell=cell, cfg=cfg, traffic=traffic, e2e=e2e,
                per_layer=per_layer, generator=generator,
                readers=readers)


def require_chip(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX reports platform {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX reports "
                     f"{len(devs)}")
    return devs


class CompileCounter:
    """Counts backend compilations and their seconds (JAX's monitoring
    events), so set-up can be told from the window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs


def memory_peak(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(args, root: str = ROOT, check_chip: bool = True,
        use_cache: bool = True, control: bool = False) -> Dict:
    """One run of ``args.workload``.  ``check_chip=False`` skips the look
    for a chip and the table of peaks (tests on the CPU);
    ``use_cache=False`` leaves JAX's persistent compilation cache alone;
    ``control=True`` puts the reference's control in the program's
    place (``bench/control.py``)."""
    import jax

    from repro import compile_cache

    parts = resolve(root, args.workload)
    cell = parts["cell"]
    if check_chip:
        devs = require_chip(cell["chips"])
    else:
        devs = jax.devices()
    devs = devs[:cell["chips"]]
    from bench import work as W

    peak = W.peaks(devs[0].device_kind) if check_chip else None
    if use_cache:
        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    rt = dict(seed=args.seed, seconds=float(args.seconds), peak=peak,
              devices=devs, workload=cell["name"], control=control)
    from bench.loop import SPAN_SECONDS, span

    SPAN_SECONDS.clear()
    t_build = time.perf_counter()
    runner = parts["generator"].build(parts["cfg"], parts["traffic"], rt)
    with span("bench.warm"):
        runner.warm()
    setup_s = time.perf_counter() - T_START
    setup_compiles, setup_compile_s = compiles.count, compiles.seconds
    phases = " ".join(f"{k[len('bench.'):]}={v:.3f}"
                      for k, v in SPAN_SECONDS.items())
    print(f"# set-up: {setup_s:.3f} s (start to build {t_build - T_START:.3f}"
          f" s; {phases}), {setup_compiles} compilations "
          f"({setup_compile_s:.3f} s), persistent cache hits="
          f"{compile_cache.STATS['hits']} writes="
          f"{compile_cache.STATS['writes']}", file=sys.stderr, flush=True)
    SPAN_SECONDS.clear()

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
    with span("bench.window"):
        win = runner.window(float(args.seconds))
    if args.trace:
        jax.profiler.stop_trace()
    SPAN_SECONDS.pop("bench.window", None)
    window_compiles = compiles.count - setup_compiles
    phases = " ".join(f"{k[len('bench.'):]}={v:.3f}"
                      for k, v in SPAN_SECONDS.items())
    print(f"# window: {win['elapsed_s']:.3f} s ({phases}), "
          f"{win['attempted']} attempted, {win['failed']} failed, "
          f"{window_compiles} compilations inside the window",
          file=sys.stderr, flush=True)
    mem_peak = memory_peak(devs)

    reduced = None
    if trace_dir is not None:
        from bench import trace as T

        reduced = T.reduce(T.load(T.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    checks = runner.check()          # frees the program's state first
    correct = bool(checks) and all(c["value"] <= c["limit"] for c in checks)
    correct = correct and window_compiles == 0 and win["failed"] == 0

    record = dict(window=win, trace=reduced, setup_s=setup_s,
                  setup_compile_s=setup_compile_s, peak=peak,
                  window_compiles=window_compiles,
                  counters=win.get("counters", {}))
    metrics: Dict[str, Dict] = {}
    if args.trace:
        for m in parts["per_layer"]:
            v = parts["readers"][m["name"]].read(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = dict(win.get("metrics", {}), setup_s=setup_s)
        for m in parts["e2e"]:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["check"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                       for c in checks}
    result["check"]["window_compilations"] = {"value": window_compiles,
                                              "limit": 0}
    return result


def parse(argv: Optional[List[str]] = None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 3
    for name, c in result["check"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

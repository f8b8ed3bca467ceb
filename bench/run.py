#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print one result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell (``BENCHMARK.json`` ``workloads``)
names a configuration file and a traffic mix under ``bench/``; each level
and the expert is of a kind, ``bench/kinds/<kind>.py`` (its reference)
and ``<kind>_program.py`` (its adapter to the program); per-layer
metrics are readers under ``bench/metrics/<name>.py``; the limits of the
correctness comparison are ``bench/limits/<cell>.json``.  Adding a cell,
mix, configuration, kind or metric is adding such files and entries.

Set-up (everything before the window, compilation included, reported as
``setup_s``) builds the repo's ``BatchedCascadeEngine`` with weights made
from the seed, drives the cell's first ticks for the correctness replay
(learning cells), and compiles every shape the cell's ticks can use.  The
window then runs for ``--seconds``; nothing may compile in it.  With
``--trace 1`` the profiler records the window and the per-layer metrics
are read from the trace.  After the window the engine is freed and the
plain reference (``check.py``) decides ``correct``.

The run exits non-zero without printing a result when JAX finds no TPU,
fewer chips than the cell asks for, or a chip missing from
``bench/peaks.json``.  It never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

import kinds

T_START = time.perf_counter()
CACHE_BYTES = 8 << 30           # room for every cell's compiled programs
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SetupError(Exception):
    """The cell cannot be run here; no result is printed."""


def load_cell(name: str) -> dict:
    """The cell's entries and files, found by name from BENCHMARK.json."""
    try:
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    except OSError as e:
        raise SetupError(f"no BENCHMARK.json at {ROOT}: {e}")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    if not (ROOT / "src" / "repro").is_dir():
        raise SetupError(f"no program sources under {ROOT / 'src'}")
    return cell_from_files(name, conf["file"], cell["traffic"],
                           cell["chips"], spec)


def cell_from_files(name: str, config_file: str, traffic: str,
                    chips: int = 1, spec: dict = None) -> dict:
    """A cell from its configuration file and traffic mix; the limits
    are ``bench/limits/<name>.json`` and the metrics those of ``spec``
    (BENCHMARK.json) that name the cell."""
    spec = spec or {"end_to_end": [], "per_layer": []}
    cfg = json.loads((ROOT / config_file).read_text())
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    if (mix["driver"], mix["regime"]) != ("lockstep", "learn"):
        raise SetupError(f"mix {traffic!r}: the harness drives lockstep "
                         "learning mixes only")
    lim_path = BENCH / "limits" / f"{name}.json"
    limits = json.loads(lim_path.read_text()) if lim_path.exists() else {}
    casc = cfg["cascade"]
    for lv in casc["levels"] + [casc["expert"]]:
        lv.setdefault("precision", casc["precision"])
        try:
            kinds.check(lv["kind"])
        except LookupError as e:
            raise SetupError(f"{config_file}: kind {lv['kind']!r}: {e}")
    for lv in casc["levels"]:
        lv["spec"] = casc.get(lv["kind"])
    metrics = [m for m in spec["per_layer"]
               if name in m.get("workloads", [name])]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    return {"name": name, "cell": {"chips": chips}, "cfg": cfg, "mix": mix,
            "limits": limits, "per_layer": metrics, "end_to_end": e2e}


def setup_jax(chips: int, check_device: bool = True):
    """Compile cache in the checkout (or where JAX_COMPILATION_CACHE_DIR
    says), then the device check.  Returns ``(jax, device info, peaks)``."""
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # a cell's programs (a BERT-width route pass is tens of MB) must all
    # stay in the cache: an LRU cap below their sum evicts each entry
    # before its next run reads it, and every run compiles again
    cap = jax.config.jax_compilation_cache_max_size
    if 0 <= cap < CACHE_BYTES:
        jax.config.update("jax_compilation_cache_max_size", CACHE_BYTES)
    peaks = json.loads((BENCH / "peaks.json").read_text())
    devs = jax.devices()
    d = devs[0]
    if check_device:
        if d.platform != "tpu":
            raise SetupError(f"JAX finds no TPU (platform {d.platform!r}); "
                             "the benchmark has no CPU fallback")
        if len(devs) < chips:
            raise SetupError(f"the cell needs {chips} chips, JAX sees "
                             f"{len(devs)}")
        if d.device_kind not in peaks:
            raise SetupError(f"device kind {d.device_kind!r} is not in "
                             "bench/peaks.json")
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    return jax, info, peaks.get(d.device_kind)


class CompileCounter:
    """Counts traces and compilations (cache hits included), and keeps
    their seconds and the persistent cache's hits and misses."""

    def __init__(self, jax):
        from jax._src import dispatch
        self.events = {dispatch.JAXPR_TRACE_EVENT,
                       dispatch.BACKEND_COMPILE_EVENT}
        self.n = 0
        self.seconds = {}
        self.counts = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._count)

    def _on(self, event, duration, **kw):
        if event in self.events:
            self.n += 1
        name = event.rsplit("/", 1)[-1]
        self.seconds[name] = self.seconds.get(name, 0.0) + duration

    def _count(self, event, **kw):
        name = event.rsplit("/", 1)[-1]
        self.counts[name] = self.counts.get(name, 0) + 1

    def report(self, when: str) -> None:
        print(f"compile stats {when}: seconds "
              f"{json.dumps(self.seconds)} counts {json.dumps(self.counts)}",
              file=sys.stderr, flush=True)


class Phases:
    """Prints the seconds each phase of a run took, on standard error."""

    def __init__(self):
        self.t = time.perf_counter()
        print(f"phase imports: {self.t - T_START:.3f} s", file=sys.stderr)

    def __call__(self, name: str) -> None:
        t = time.perf_counter()
        print(f"phase {name}: {t - self.t:.3f} s", file=sys.stderr,
              flush=True)
        self.t = t


def load_reader(name: str):
    """A per-layer metric's reader, ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(c: dict, seed: int, seconds: float, trace: bool,
             check_device: bool = True, control: bool = False,
             keep_trace: str = "", keep: dict = None) -> dict:
    """Set up, measure and check one run; returns the result dict.
    ``keep``, where given, receives the weights and the check's evidence
    (``bench/readings.py``)."""
    mix, cfg = c["mix"], c["cfg"]
    jax, dev, peak = setup_jax(c["cell"]["chips"], check_device)
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import check
    import drivers
    import model
    import system
    import traffic

    compiles = CompileCounter(jax)
    casc = cfg["cascade"]
    phases = Phases()
    docs, _ = traffic.corpus(mix, seed)
    phases("corpus")
    weights = model.make_weights(casc, seed)
    weights["expert"] = model.balance_expert(weights["expert"], casc,
                                             docs[:256])
    jax.block_until_ready(weights)
    phases("weights")
    eng = system.build(casc, mix, seed, weights)
    rec = system.RouteRecorder(eng)
    run = drivers.Run(eng, rec, mix, docs, seed, jax)
    phases("engine")
    run.replay_ticks(mix["check"]["replay_ticks"], weights, casc)
    phases("replay ticks")
    run.warm(compiles)
    phases("warm-up")
    setup_s = time.perf_counter() - T_START
    compiles.report("set-up")

    trace_dir = None
    if trace:
        trace_dir = Path(keep_trace) if keep_trace else (
            ROOT / ".bench_trace" / c["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(trace_dir))
    compiles.n = 0
    w = run.window_lockstep(seconds)
    in_window = compiles.n
    if trace:
        jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    # the engine and its state are freed before the reference runs
    evidence = run.evidence()
    del run, eng, rec
    gc.collect()

    phases("window")
    numbers, faults = check.learning_numbers(
        cfg, mix, seed, weights, evidence["docs_by_tick"],
        evidence["outs_by_tick"], evidence["calls"],
        evidence["prog_grads"], evidence["prog_deltas"],
        evidence["window_labels"], control=control)
    phases("reference check")
    compiles.report("run")
    if keep is not None:
        keep.update(weights=weights, evidence=evidence)
    correct = check.verdict(numbers, c["limits"], len(evidence["calls"]))
    if in_window:
        correct = False

    metrics = {}
    if not trace:
        for m in c["end_to_end"]:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": m["unit"]}
            elif m["name"] in w["e2e"]:
                metrics[m["name"]] = {"value": w["e2e"][m["name"]],
                                      "unit": m["unit"]}
    else:
        import xplane as tr
        path = tr.find_xplane(str(trace_dir))
        red = tr.reduce_trace(path) if path else None
        ctx = {"trace": red, "window": w, "peak": peak, "cfg": cfg,
               "mix": mix}
        for m in c["per_layer"]:
            v = load_reader(m["name"])(ctx)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            dev["busy_s"] = red["busy_s"]
            dev["window_s"] = red["window_s"]
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        phases("trace reduction")
    out = {"correct": bool(correct), "attempted": w["attempted"],
           "failed": w["failed"], "metrics": metrics, "device": dev}
    if trace and red is not None:
        out["breakdown"] = tr.breakdown(red)
    out["info"] = {"compiles_in_window": in_window, **w["info"],
                   "readings": {**{k: v for k, v in numbers.items()
                                   if k not in c["limits"]}, **faults}}
    out["checks"] = {k: {"value": numbers.get(k), "limit": v}
                     for k, v in c["limits"].items()}
    return out


def main(argv=None) -> int:
    """Parse the arguments, run the cell, print the result line."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the reference one precision lower in the "
                         "program's place (the control of PERF.md)")
    ap.add_argument("--keep-trace", default="",
                    help="write the profile here and keep it")
    a = ap.parse_args(argv)
    try:
        c = load_cell(a.workload)
        out = run_cell(c, a.seed, a.seconds, bool(a.trace),
                       control=a.control, keep_trace=a.keep_trace)
    except SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(f"compiles in window: {out['info']['compiles_in_window']}",
          file=sys.stderr)
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one benchmark cell, found by name and driven by data.

``BENCHMARK.json`` names the cell's configuration file and traffic mix;
the mix names its engine (``chipbench/engines/<engine>.py``); each
per-layer metric is read by ``chipbench/metrics/<metric>.py``.  A cell
added as files alone runs with no edit here.

A run: set-up (inputs, the engine's host lowering, warm-up answers that
compile every program the window uses), then answers back to back for
``--seconds``, each one call into the engine ended by
``block_until_ready``; then, with the program's state freed, the check of
sampled answers against the plain reference.  With ``--trace 1`` the
window is a short profiled one and the result carries the per-layer
metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import argparse
import collections
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from chipbench import trace

SPEC = "BENCHMARK.json"
TRAFFIC_DIR = Path("chipbench/traffic")
ENGINE_DIR = Path("chipbench/engines")
METRIC_DIR = Path("chipbench/metrics")
PEAKS = Path("chipbench/peaks.json")
CACHE = ".jax_cache"

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """The machine lacks what the cell needs; the run prints no result."""


class CompileMeter:
    """Sums JAX's compile-event durations and counts backend compiles
    (persistent-cache loads included) per jitted function name."""

    def __init__(self) -> None:
        import jax
        self.seconds = 0.0
        self.programs: collections.Counter = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration
        if event == _COMPILE_EVENTS[-1]:
            self.programs[kwargs.get("fun_name", "?")] += 1

    @property
    def compiles(self) -> int:
        return sum(self.programs.values())

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


def _load_module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _load_json(path: Path) -> Dict:
    with open(path) as fh:
        return json.load(fh)


def find_cell(root: Path, name: str) -> SimpleNamespace:
    """The cell's entry, configuration, traffic and engine, by name."""
    spec = _load_json(root / SPEC)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {SPEC}: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    traffic = _load_json(root / TRAFFIC_DIR / f"{cell['traffic']}.json")
    return SimpleNamespace(
        name=name, spec=spec, chips=int(cell["chips"]),
        config=_load_json(root / configs[cell["config"]]["file"]),
        traffic=traffic,
        engine=_load_module(root / ENGINE_DIR / f"{traffic['engine']}.py"))


def cell_metrics(spec: Dict, cell: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in spec[kind] if cell in m.get("workloads", [cell])]


def devices_for(chips: int, peaks: Dict, require_tpu: bool):
    """The cell's devices and their peaks; a missing chip is ``NoChip``."""
    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
        if len(devices) < chips:
            raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
        if kind not in peaks["devices"]:
            raise NoChip(f"no peaks for device kind {kind!r} in {PEAKS}")
    return devices[:chips], peaks["devices"].get(kind)


def _answer(engine, index: int):
    import jax
    with jax.profiler.TraceAnnotation(trace.ANSWER_SPAN):
        out = engine.answer(index)
        jax.block_until_ready(out)
    return out


def _memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(root: Path, workload: str, seed: int, seconds: float, traced: bool,
        t_start: float, require_tpu: bool = True) -> Dict:
    """One run of one cell; returns the result line as a dict."""
    import jax
    cell = find_cell(root, workload)
    devices, peak = devices_for(cell.chips, _load_json(root / PEAKS),
                                require_tpu)
    t = cell.traffic
    meter = CompileMeter()
    try:
        t_chip = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.setup"):
            engine = cell.engine.Engine(cell.config, t, seed)
            t_engine = time.perf_counter()
            n_warm = t.get("warmup_answers", 1)
            for i in range(n_warm):
                _answer(engine, i)
        setup_s = time.perf_counter() - t_start
        setup_parts = {"start_and_chip_s": t_chip - t_start,
                       "engine_s": t_engine - t_chip,
                       "warmup_s": t_start + setup_s - t_engine,
                       "compile_s": meter.seconds}
        setup_compile_s, compiles0 = meter.seconds, meter.compiles

        durations, kept, summary = [], [], None
        tmp = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
        if traced:
            jax.profiler.start_trace(tmp)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            while not durations or time.perf_counter() - t0 < seconds:
                if traced and len(durations) >= t.get("trace_answers", 2):
                    break
                a = time.perf_counter()
                out = _answer(engine, n_warm + len(durations))
                durations.append(time.perf_counter() - a)
                kept.append(engine.keep(out))
                del out
        window_s = time.perf_counter() - t0
        if traced:
            jax.profiler.stop_trace()
            summary = trace.read_xplane(tmp, cell.chips)
            shutil.rmtree(tmp, ignore_errors=True)
        window_compiles = meter.compiles - compiles0
        memory = _memory_peak(devices)
    finally:
        meter.close()

    with jax.profiler.TraceAnnotation("chipbench.check"):
        gaps = engine.check(kept)
    checks = gaps.rows()
    correct = not gaps.failed

    if traced:
        ctx = SimpleNamespace(
            engine=engine, peak=peak, answers=len(durations), summary=summary,
            setup_compile_s=setup_compile_s, window_compiles=window_compiles)
        metrics = {}
        for m in cell_metrics(cell.spec, workload, "per_layer"):
            reader = _load_module(root / METRIC_DIR / f"{m['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        known = {"setup_s": setup_s,
                 "answers_per_s": len(durations) / window_s,
                 "answer_p95_s": float(np.percentile(durations, 95)),
                 "device_peak_bytes": memory}
        metrics = {m["name"]: {"value": known[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(cell.spec, workload, "end_to_end")}

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory}
    result = {"correct": correct, "attempted": len(durations),
              "failed": len(gaps.failed), "metrics": metrics,
              "device": device}
    if traced:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
    result["setup_parts"] = setup_parts
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` or at the
    checkout's fixed ``.jax_cache``, keeping every program it compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv: List[str], t_start: float, root: Path) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    enable_compile_cache(root)
    try:
        result = run(root, args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0

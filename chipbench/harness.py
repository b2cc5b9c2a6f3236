"""Runs one cell of ``BENCHMARK.json`` and prints its result line.

Everything a cell needs is found by name: its configuration file (the
``file`` of its entry in ``configs``), which names its plain reference
(``reference/<reference>.py``) and its FLOP count (``<flops>.py``,
``flops.py`` unless it says otherwise); its traffic mix
(``traffic/<traffic>.json``, whose ``kind`` names the runner module
``<kind>_cell.py``); its limits (``limits/<workload>.json``); and one
reader per per-layer metric (``metrics/<metric>.py``).  A new cell, mix,
configuration, reference, FLOP count or metric is a new file and a new
entry; no file here changes.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from chipbench import compare, reduce, traffic
from chipbench.peaks import peaks_for

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def span(name: str):
    """A host span in the profiler's trace (no cost when it is off)."""
    import jax

    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


@dataclasses.dataclass
class Run:
    """What a runner measured, handed to the per-layer metric readers."""

    kind: str
    chips: int
    peak: dict
    sizes: dict
    mix: dict
    metrics: Dict[str, tuple]  # end-to-end name -> (value, unit)
    numbers: Dict[str, float]  # compared numbers (see compare.py)
    attempted: int
    failed: int
    memory_peak_bytes: int
    window_s: float  # host clock, the window's start to its last step done
    flops: float = 0.0  # model FLOPs done in the window
    trace: Optional[reduce.Trace] = None
    trace_window: Optional[tuple] = None  # (lo, hi) ns on the trace clock
    # the optimized HLO text of the step the window drove (None: no step to map)
    step_hlo: Optional[Callable[[], str]] = None
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)


class Context:
    """One run's inputs and the hooks a runner calls around its window."""

    def __init__(self, workload: str, seed: int, seconds: float, trace: bool,
                 t_start: float, root: str = REPO_ROOT, require_chip: bool = True):
        self.bench_dir = os.path.join(root, "chipbench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
        self.cell = cells[workload]
        cfgs = {c["name"]: c for c in self.bench["configs"]}
        with open(os.path.join(root, cfgs[self.cell["config"]]["file"])) as f:
            self.sizes = json.load(f)
        self.mix = traffic.load(self.cell["traffic"], self.bench_dir)
        self.limits = compare.load_limits(workload, self.bench_dir)
        self.workload, self.seed, self.seconds, self.trace = workload, seed, seconds, trace
        self.chips = self.cell["chips"]
        self.t_start = t_start
        self.require_chip = require_chip
        self.compiles: List[float] = []
        self._trace_dir: Optional[str] = None
        self._window_span = None
        self.devices = self._devices()
        self.peak = peaks_for(self.devices[0].device_kind) if require_chip else {
            "bf16_flops_per_s": 197e12, "source": "test stand-in, no chip"}

    def _devices(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        devs = jax.devices()
        if self.require_chip:
            if devs[0].platform != "tpu":
                raise NoChip(f"no TPU found (platform {devs[0].platform!r})")
            peaks_for(devs[0].device_kind)  # raises for a kind not in the table
            if len(devs) < self.chips:
                raise NoChip(f"cell needs {self.chips} chips, found {len(devs)}")
        return devs[: self.chips]

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append(time.perf_counter())

    def program_config(self):
        """The program's ModelConfig with every size the file states."""
        import jax.numpy as jnp
        from repro.configs import load_config

        cfg = load_config(self.sizes["arch"])
        names = {f.name for f in dataclasses.fields(cfg)}
        over = {k: v for k, v in self.sizes.items()
                if k in names and k not in ("name", "source", "dtype", "param_dtype")}
        return dataclasses.replace(cfg, **over, dtype=jnp.dtype(self.sizes["dtype"]),
                                   param_dtype=jnp.dtype(self.sizes["param_dtype"]))

    def reference(self):
        """The configuration's plain reference, ``reference/<reference>.py``."""
        return load_module(os.path.join(self.bench_dir, "reference",
                                        f"{self.sizes['reference']}.py"))

    def flops(self):
        """The configuration's FLOP count, ``<flops>.py`` (``flops.py``
        where the file names none)."""
        return load_module(os.path.join(self.bench_dir, f"{self.sizes.get('flops', 'flops')}.py"))

    def window_begin(self) -> float:
        """Start the profiler (traced runs) and the window span; returns
        the window's start on the host clock."""
        import jax

        if self.trace:
            self._trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self._trace_dir)
        self._window_span = span("window")
        self._window_span.__enter__()
        return time.perf_counter()

    def window_end(self) -> Optional[reduce.Trace]:
        """Close the window span, stop the profiler and reduce its trace."""
        import jax

        self._window_span.__exit__(None, None, None)
        if not self.trace:
            return None
        jax.profiler.stop_trace()
        try:
            paths = [os.path.join(d, f) for d, _, fs in os.walk(self._trace_dir)
                     for f in fs if f.endswith(".xplane.pb")]
            return reduce.from_xspace(paths[0], [d.id for d in self.devices])
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    def compiles_between(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t in self.compiles)


class NoChip(RuntimeError):
    pass


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def free_device_arrays() -> None:
    """Delete every array still on a device: the reference runs only once
    the program's state is gone."""
    import gc

    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()


@functools.lru_cache(maxsize=None)
def load_module(path: str):
    """The Python file at ``path`` as a module, loaded once per process."""
    name = "chipbench_file_" + os.path.splitext(os.path.basename(path))[0].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_reader(bench_dir: str, name: str) -> Callable[[Run], Optional[float]]:
    return load_module(os.path.join(bench_dir, "metrics", f"{name}.py")).read


def _applies(metric: dict, workload: str, e2e_here: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_here


def cell_end_to_end(bench: dict, workload: str) -> List[dict]:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]]


def result(ctx: Context, run: Run) -> dict:
    """The result line's object; ``checks`` comes last."""
    e2e = cell_end_to_end(ctx.bench, ctx.workload)
    names = {m["name"] for m in e2e}
    missing = names - set(run.metrics)
    if missing:
        raise RuntimeError(f"runner reported no {sorted(missing)}")
    device = {"platform": ctx.devices[0].platform, "kind": ctx.devices[0].device_kind,
              "count": len(ctx.devices), "memory_peak_bytes": run.memory_peak_bytes}
    out: Dict[str, Any] = {}
    if ctx.trace:
        metrics = {}
        for m in ctx.bench["per_layer"]:
            if not _applies(m, ctx.workload, names):
                continue
            value = _load_reader(ctx.bench_dir, m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        lo, hi = run.trace_window
        device["busy_s"] = reduce.busy_ns(run.trace, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {"device_ops": reduce.top_ops(run.trace, lo, hi),
                            "idle_gaps": reduce.idle_gaps(run.trace, lo, hi)}
    else:
        metrics = {m["name"]: {"value": float(run.metrics[m["name"]][0]), "unit": m["unit"]}
                   for m in e2e}
    chk = compare.checks(run.numbers, ctx.limits)
    line = {"correct": compare.all_within(chk) and run.failed == 0,
            "attempted": run.attempted, "failed": run.failed,
            "metrics": metrics, "device": device}
    line.update(out)
    line["checks"] = chk
    return line


def runner_module(kind: str):
    """``chipbench/<kind>_cell.py``: its ``run`` and what calibration reads."""
    return importlib.import_module(f"chipbench.{kind}_cell")


def runner(kind: str) -> Callable[[Context], Run]:
    """The ``run`` of ``chipbench/<kind>_cell.py``."""
    return runner_module(kind).run


def parse(argv: List[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(argv: List[str], t_start: float, root: str = REPO_ROOT,
             require_chip: bool = True) -> dict:
    args = parse(argv)
    ctx = Context(args.workload, args.seed, args.seconds, bool(args.trace), t_start,
                  root=root, require_chip=require_chip)
    run = runner(ctx.mix["kind"])(ctx)
    return result(ctx, run)


def main(argv: List[str], t_start: float) -> int:
    try:
        line = run_cell(argv, t_start)
    except NoChip as e:
        log(f"refusing to run: {e}; there is no CPU fallback")
        return 3
    for name, c in line["checks"].items():
        ok = np.isfinite(c["value"]) and c["value"] <= c["limit"]
        print(f"check {name} value={c['value']!r} limit={c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0

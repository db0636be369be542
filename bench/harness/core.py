"""One run of one cell: find its files by name, drive it, print the result.

``BENCHMARK.json`` names each cell's configuration and traffic.  The
harness reads ``bench/configs/<config>.json`` and
``bench/traffic/<traffic>.json``; the traffic file names its kind,
``bench/kinds/<kind>.py``, which builds the system, warms it up and
drives the measured window; and each metric ``<name>`` that the cell
reports is read by ``bench/metrics/<name>.py``.  A cell added later brings
its own files and a ``workloads`` entry, and edits none of these.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"
# a --trace 1 run traces the window's last 5 s (and a serve cell's drain
# after the close): a whole serve window traces some 170,000 device ops a
# second, which takes minutes to read back, and stopping and reading the
# trace inside the window would stall it
TRACE_SECONDS = 5.0

# lowering a jaxpr to a module happens once for every program that is
# compiled or fetched from the persistent cache, and never for a call that
# hits jit's in-memory cache: so its count is the count of compilations
_COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileCounter:
    """Counts the process's compilations through ``jax.monitoring``.  JAX
    keeps listeners for the life of the process, so one counter is made
    per process and shared (``compile_counter()``)."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == _COMPILE_EVENT:
            self.count += 1


_COUNTER: Optional[CompileCounter] = None


def compile_counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file by path (metric readers' names hold dots), once."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Run:
    """What a kind module hands the metric readers.  It sets the fields of
    their kind (requests, plans, ...); the harness sets ``setup_s``,
    ``window_s``, ``compiles_in_window``, ``peaks``, and with a trace
    ``trace`` and ``traced``, the traced slice in seconds from the
    window's start."""

    def __init__(self, cell: "Cell"):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.peaks = cell.peaks
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.compiles_in_window: Optional[int] = None
        self.trace = None
        self.traced = None              # (start, stop) s from window start
        self.attempted = 0
        self.failed = 0
        self.checks: List[Dict[str, Any]] = []
        self.memory_peak_bytes: Optional[int] = None

    def check(self, name: str, value: float, limit: float):
        """One compared number: correct while ``value <= limit``."""
        self.checks.append({"name": name, "value": float(value),
                            "limit": float(limit)})

    def fresh(self) -> "Run":
        """A run of the same cell and work with no checks yet: where a
        control puts its reading."""
        out = Run(self.cell)
        out.attempted, out.failed = self.attempted, self.failed
        return out

    @property
    def correct(self) -> bool:
        return bool(self.checks) and self.failed == 0 and all(
            c["value"] <= c["limit"] for c in self.checks)


class Cell:
    """One cell's files and arguments, and the measured window."""

    def __init__(self, workload: dict, config: dict, traffic: dict, *,
                 seed: int, seconds: float, trace: bool, peaks: dict,
                 t_start: float, host_spans: List[str],
                 trace_dir: Path = TRACE_DIR):
        self.workload = workload
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.peaks = peaks
        self.t_start = t_start
        self.host_spans = host_spans
        self.trace_dir = Path(trace_dir) / workload["name"]
        self._t0: Optional[float] = None
        self._traced: Optional[List[float]] = None
        self.run = Run(self)

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends as it opens; compilations are
        counted inside it.  With ``--trace 1`` the kind module's calls to
        :meth:`poll` start the trace for its last ``TRACE_SECONDS``; the
        trace stops, and is read, once the window has closed."""
        counter = compile_counter()
        c0 = counter.count
        self._t0 = t0 = time.perf_counter()
        self.run.setup_s = t0 - self.t_start
        try:
            yield t0
        finally:
            self.run.window_s = time.perf_counter() - t0
            self.run.compiles_in_window = counter.count - c0
            self._stop_trace()

    def poll(self):
        """Called by the kind module between steps of the window: starts
        the trace, so that the traced slice holds whole steps."""
        if not self.trace or self._t0 is None or self._traced is not None:
            return
        now = time.perf_counter() - self._t0
        if now >= self.seconds - TRACE_SECONDS:
            import jax
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.trace_dir))
            self._span = jax.profiler.TraceAnnotation("traced")
            self._span.__enter__()
            self._traced = [time.perf_counter() - self._t0, None]

    def _stop_trace(self):
        if self._traced is None or self._traced[1] is not None:
            return
        import jax
        from bench.harness import trace as tr
        self._span.__exit__(None, None, None)
        self._traced[1] = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        self.run.traced = tuple(self._traced)
        self.run.trace = tr.load_xplane(str(self.trace_dir), self.host_spans,
                                        window_name="traced")
        shutil.rmtree(self.trace_dir, ignore_errors=True)


def devices_for(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU: {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} devices, the cell asks for {chips}")
    return devs[:chips]


def memory_peak(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def metric_names(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics a cell reports: its end-to-end ones without a trace,
    its per-layer ones with."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}

    def applies(m):
        if "workloads" in m:
            return workload in m["workloads"]
        return m["moves"] in moved
    return [m for m in bench["per_layer"] if applies(m)]


def find(dirs: Sequence[Path], sub: str, name: str) -> Path:
    """``<dir>/<sub>/<name>`` from the first of ``dirs`` that has it."""
    for d in dirs:
        p = Path(d) / sub / name
        if p.exists():
            return p
    raise FileNotFoundError(f"no {sub}/{name} under {list(map(str, dirs))}")


def load_kind(name: str, dirs: Sequence[Path] = (BENCH,)):
    return load_module(find(dirs, "kinds", f"{name}.py"),
                       f"bench_kind_{name}")


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench_file: Path = ROOT / "BENCHMARK.json",
             dirs: Sequence[Path] = (BENCH,),
             require_chip: bool = True,
             cache_dir: Optional[Path] = CACHE_DIR,
             trace_dir: Path = TRACE_DIR,
             t_start: Optional[float] = None,
             traffic: Optional[dict] = None):
    """Run one cell once; returns its result line (a dict) and the
    :class:`Run` the metrics were read from.  ``dirs`` are searched in
    order for each file a cell names; ``traffic`` stands in for the cell's
    traffic file (a sweep's rates)."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = load_json(bench_file)
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in {bench_file}")
    config = load_json(find(dirs, "configs", f"{wl['config']}.json"))
    if traffic is None:
        traffic = load_json(find(dirs, "traffic", f"{wl['traffic']}.json"))
    peaks_table = load_json(BENCH / "peaks.json")

    import jax
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = devices_for(int(wl["chips"]), require_chip)
    kind = devs[0].device_kind
    if require_chip and kind not in peaks_table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    peaks = peaks_table["devices"].get(kind)

    module = load_kind(traffic["kind"], dirs)
    cell = Cell(wl, config, traffic, seed=seed, seconds=seconds,
                trace=trace, peaks=peaks, t_start=t_start,
                host_spans=list(module.HOST_SPANS), trace_dir=trace_dir)
    run = cell.run
    module.run(cell, devs)

    metrics = {}
    for m in metric_names(bench, workload, trace):
        reader = load_module(find(dirs, "metrics", f"{m['name']}.py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    out = verdict(run)
    checks = out.pop("checks")          # last in the line
    out.update(metrics=metrics, device=device)
    if trace and run.trace is not None:
        from bench.harness import trace as tr
        device["busy_s"] = tr.busy_s(run.trace)
        device["window_s"] = tr.window_s(run.trace)
        out["breakdown"] = {"device_ops": tr.top(tr.op_seconds(run.trace)),
                            "idle_gaps": tr.idle_gaps(run.trace)}
    out["checks"] = checks
    return out, run


def verdict(run: Run) -> dict:
    """What a result line says of a run's correctness, its compared
    numbers each beside its limit."""
    return {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed,
            "checks": {c["name"]: {"value": c["value"], "limit": c["limit"]}
                       for c in run.checks}}


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        out, _ = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0

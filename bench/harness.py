"""The benchmark harness: one run of one cell, found by name.

Everything that belongs to one cell, configuration, traffic mix or
per-layer metric lives in files of its own, found by the names in
``BENCHMARK.json``:

* a configuration is ``configs/<name>.json`` (its ``file`` entry), whose
  ``reference`` names its plain reference, ``references/<reference>.py``;
* a traffic mix is ``traffic/<name>.json``, whose ``driver`` names the
  general generator that reads it, ``drivers/<driver>.py``;
* a per-layer metric is ``metrics/<name>.py``, with a ``read(reading)``
  that returns the value, or None where it finds nothing to read.

A driver module has ``setup(ctx)``, ``window(ctx, state, seconds)`` and
``check(ctx, state)``; see ``drivers/fit_loop.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time

def load_module(path: pathlib.Path):
    """Import a Python file by path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    root: pathlib.Path
    bench: dict
    workload: dict
    config: dict
    traffic: dict

    @classmethod
    def find(cls, root: pathlib.Path, name: str) -> "Cell":
        bench = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        workload = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        config = json.loads(
            (root / configs[workload["config"]]["file"]).read_text())
        traffic = json.loads(
            (root / "bench" / "traffic" / f"{workload['traffic']}.json")
            .read_text())
        return cls(root, bench, workload, config, traffic)

    def _applies(self, metric: dict) -> bool:
        return self.workload["name"] in metric.get(
            "workloads", [w["name"] for w in self.bench["workloads"]])

    @property
    def end_to_end(self) -> list:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.bench["end_to_end"] if self._applies(m)]

    @property
    def per_layer(self) -> list:
        """The per-layer metrics this cell reports in a traced run."""
        e2e = {m["name"] for m in self.end_to_end}
        return [m for m in self.bench["per_layer"]
                if m["moves"] in e2e and self._applies(m)]

    @property
    def dir(self) -> pathlib.Path:
        """The benchmark's directory in this checkout."""
        return self.root / "bench"

    def driver(self):
        return load_module(self.dir / "drivers"
                           / f"{self.traffic['driver']}.py")

    def reference(self):
        """The configuration's plain reference module (imported once, so
        that its jitted functions keep their compiled programs)."""
        if "_reference" not in self.__dict__:
            self.__dict__["_reference"] = load_module(
                self.dir / "references" / f"{self.config['reference']}.py")
        return self.__dict__["_reference"]


@dataclasses.dataclass
class Context:
    """What a driver sees of its run."""

    cell: Cell
    seed: int
    tracing: bool = False
    say: object = print
    control: dict | None = None     # the configuration's control, when run

    @property
    def cfg(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def span(self, name: str):
        """A host span ``bench.<name>`` in the trace (a no-op untraced)."""
        import contextlib

        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)


class CompileCounter:
    """Counts lowerings to XLA (each a compile or a compile-cache load)."""

    _EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax import monitoring

        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == self._EVENT:
            self.count += 1


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader sees: the reduced trace, the window
    bounds on its clock, the driver's window record and the cell."""

    trace: object
    lo: int
    hi: int
    window: dict
    cfg: dict
    traffic: dict
    device_kind: str

    @property
    def peak(self) -> dict:
        """The chip's peaks (``peaks.json``); an unknown device raises."""
        import cost

        return cost.peaks(self.device_kind)


def platform_error(chips: int) -> str | None:
    """Why this process cannot run the cell, or None."""
    import jax

    devices = jax.devices()
    found = f"{devices[0].platform} ({devices[0].device_kind}) x{len(devices)}"
    if devices[0].platform != "tpu":
        return f"needs a TPU; JAX found {found}"
    if len(devices) < chips:
        return f"needs {chips} TPU chips; JAX found {found}"
    return None


def _fmt_checks(checks: list) -> dict:
    return {c["name"]: {"value": c["value"], "limit": c["limit"]}
            for c in checks}


def _passed(check: dict) -> bool:
    v = check["value"]
    return v is not None and math.isfinite(v) and v <= check["limit"]


def run_env(root: pathlib.Path, cell: Cell, control: bool = False) -> None:
    """Point JAX at the checkout's compile cache and the configuration's
    matmul precision (its control's, with ``control``), and the imports at
    the checkout's ``src``."""
    matmul = (cell.config["control"] if control
              else cell.config["precision"])["matmul"]
    cache = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["JAX_DEFAULT_MATMUL_PRECISION"] = matmul
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_default_matmul_precision", matmul)


def run(root: pathlib.Path, workload: str, seed: int, seconds: float,
        trace: bool, *, require_tpu: bool = True, t_start: float | None = None,
        control: bool = False, out=None, err=None) -> int:
    """One run of one cell; prints the result line; returns the exit code.

    With ``control`` the configuration's ``control`` (the program at the
    next precision down) runs in the program's place, and the comparison
    is the same: its result line has to read ``correct: false``."""
    out = out or sys.stdout
    err = err or sys.stderr
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell.find(root, workload)
    run_env(root, cell, control)
    import jax

    if require_tpu:
        why = platform_error(cell.workload["chips"])
        if why is not None:
            print(f"bench: {why}", file=err)
            return 3
    if jax.config.jax_enable_x64:
        print("bench: the configurations are float32; unset JAX_ENABLE_X64",
              file=err)
        return 3

    def say(msg):
        print(msg, file=out, flush=True)

    ctx = Context(cell, seed, tracing=trace, say=say,
                  control=cell.config["control"] if control else None)
    driver = cell.driver()
    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    say(f"setup: {setup_s:.3f} s")

    counter = CompileCounter()
    gc.collect()
    gc.freeze()          # set-up's objects stay out of the window's GC passes
    log_dir = root / ".bench_trace"
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(log_dir), profiler_options=options)
    compiles0 = counter.count
    try:
        with ctx.span("window"):
            window = driver.window(ctx, state, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles = counter.count - compiles0
    say(f"compiles_in_window: {compiles}")
    devices = jax.devices()[:cell.workload["chips"]]
    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                       for s in stats)}

    result = {"attempted": window["attempted"], "failed": window["failed"]}
    if trace:
        from devtrace import idle_gaps, load, top

        tr = load(str(log_dir))
        spans = [s for s in tr.spans if s[0] == "bench.window"]
        lo, hi = spans[-1][1], spans[-1][2]
        device["busy_s"] = tr.busy_s(lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        reading = Reading(tr, lo, hi, window, cell.config, cell.traffic,
                          device["kind"])
        metrics = {}
        for m in cell.per_layer:
            value = load_module(cell.dir / "metrics" / f"{m['name']}.py").read(
                reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        inside = [ev for ev in tr.ops if lo <= ev[1] < hi]
        result["breakdown"] = {
            "device_ops": top(inside),
            "idle_gaps": idle_gaps(inside, tr.spans, lo, hi)}
        shutil.rmtree(log_dir, ignore_errors=True)
    else:
        values = dict(window["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    for name, m in metrics.items():
        say(f"metric {name}: {m['value']} {m['unit']}")

    checks = driver.check(ctx, state)
    correct = all(_passed(c) for c in checks)
    if control:
        result["control"] = cell.config["control"]
    print(json.dumps({"correct": correct, **result, "metrics": metrics,
                      "device": device, "checks": _fmt_checks(checks)}),
          file=out, flush=True)
    for c in checks:
        print(f"check {c['name']}: {c['value']} (limit {c['limit']})"
              f"{'' if _passed(c) else ' FAIL'}", file=err, flush=True)
    return 0

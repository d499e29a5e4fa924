"""The data-driven harness behind `bench/run.py`.

Finds a cell's configuration, traffic mix, system driver and metrics by
name, checks the devices, times set-up and the window, reduces the trace,
and assembles the result line. Nothing here knows a cell, a configuration
or a metric by name: those live in files of their own.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time

from . import traffic, xplane

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoDevice(RuntimeError):
    """No TPU, too few chips, or a served kernel that ran interpreted."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def load_config(root: str, spec: dict, name: str) -> dict:
    for entry in spec["configs"]:
        if entry["name"] == name:
            path = os.path.join(root, entry["file"])
            with open(path) as f:
                cfg = json.load(f)
            cfg["name"] = name
            cfg["dir"] = os.path.dirname(path)
            return cfg
    raise SystemExit(f"bench: no configuration {name!r} in BENCHMARK.json")


def load_module(kind: str, name: str):
    """`bench/<kind>/<name>.py` as a module (names may hold `.` and `-`)."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"bench: no {kind} file {path}")
    mod_name = f"bench_{kind}_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_peaks(device_kind: str) -> dict:
    """The chip's peaks from `bench/peaks.json`; an unknown kind is an
    error, never a default."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def cell_metrics(spec: dict, cell: str, *, per_layer: bool) -> list[dict]:
    """The metrics a cell reports. An end-to-end metric without a
    `workloads` key is in every cell; a per-layer one without it is in
    every cell that reports the end-to-end metric it moves."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not per_layer:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names
                             else [])]


# ---------------------------------------------------------------------------
# devices and compilation
# ---------------------------------------------------------------------------


def require_devices(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU (JAX platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where `JAX_COMPILATION_CACHE_DIR` says). Every compile is kept,
    the small eager ones too, so only a checkout's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compiles (and persistent-cache loads) while `armed`."""

    def __init__(self):
        self.armed = False
        self.compiles = 0
        self.cache_loads = 0
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if not self.armed:
            return
        if event.endswith("backend_compile_duration"):
            self.compiles += 1
        elif event.endswith("cache_retrieval_time_sec"):
            self.cache_loads += 1


class KernelSpy:
    """Records, for each served Pallas entry point, whether each trace of
    it was built for the interpreter (from `chip_smoke.py`'s check)."""

    def __init__(self, kernels: dict[str, tuple[str, str]]):
        self.kernels = kernels
        self.traces: dict[str, list[bool]] = {k: [] for k in kernels}

    def install(self) -> None:
        from repro.kernels.backend import resolve_interpret
        for name, (mod_name, attr) in self.kernels.items():
            mod = importlib.import_module(mod_name)
            fn = getattr(mod, attr)

            def traced(*a, _fn=fn, _name=name, **kw):
                self.traces[_name].append(resolve_interpret(
                    kw.get("interpret")))
                return _fn(*a, **kw)

            setattr(mod, attr, traced)

    def check(self) -> None:
        from repro.kernels.backend import current_policy
        mode = current_policy().resolve()
        if mode != "compiled":
            raise NoDevice(f"kernel policy resolved to {mode!r}, not "
                           "'compiled'")
        bad = {k: sum(v) for k, v in self.traces.items() if any(v)}
        if bad:
            raise NoDevice(f"served kernels ran interpreted: {bad}")


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunContext:
    """What a per-layer metric reader may look at."""

    config: dict
    mix: dict
    window: dict               # the system's window record
    peaks: dict                # this chip's row of bench/peaks.json
    spans: list                # the program's span events (Chrome format)
    reduced: dict              # xplane.reduce_trace of the window


@contextlib.contextmanager
def _profiled(log_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    shutil.rmtree(log_dir, ignore_errors=True)
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def run_cell(root: str, spec: dict, cell: dict, *, seed: int,
             seconds: float, trace: bool, device: dict,
             t_start: float, check_kernels: bool = True,
             system_factory=None, mixes: str = BENCH) -> dict | None:
    """Set up, measure and check one cell; returns the result dict.

    Tests drive a run on the CPU through `check_kernels=False`, a
    `system_factory` that breaks the timed path, and their own `mixes`
    directory (holding `traffic/<name>.json`)."""
    import jax
    config = load_config(root, spec, cell["config"])
    mix = traffic.load_mix(mixes, cell["traffic"])
    system_mod = load_module("systems", config["system"])
    spy = KernelSpy(getattr(system_mod, "KERNELS", {}))
    if check_kernels:
        spy.install()
    counter = CompileCounter()
    make = system_factory or system_mod.System
    system = make(config, mix, seed)
    system.setup()
    if check_kernels:
        try:
            spy.check()
        except NoDevice as e:
            print(f"bench: {e}", file=sys.stderr)
            return None
    setup_s = time.perf_counter() - t_start
    log_dir = os.path.join(root, "bench_out", "trace",
                           f"{cell['name']}-{seed}")
    spans: list = []
    counter.armed = True
    print(f"bench: set-up {setup_s:.1f} s; the window opens",
          file=sys.stderr, flush=True)
    if trace:
        from repro.obs.trace import Tracer, use_tracer
        tracer = Tracer(jax_profiler=True, max_events=2_000_000)
        with _profiled(log_dir), use_tracer(tracer):
            with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
                window = system.run(seconds)
        spans = tracer.events()
    else:
        window = system.run(seconds)
    counter.armed = False
    device = dict(device, memory_peak_bytes=memory_peak_bytes(cell["chips"]))
    checks = system.verify()
    print(f"bench: window {window['t1'] - window['t0']:.3f} s, "
          f"{counter.compiles} compiles and {counter.cache_loads} cache "
          "loads inside it", file=sys.stderr)
    result = {"correct": all(c["ok"] for c in checks),
              "attempted": int(window["attempted"]),
              "failed": int(window["failed"])}
    metrics: dict = {}
    if trace:
        reduced = xplane.reduce_trace(xplane.load(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        ctx = RunContext(config, mix, window, load_peaks(device["kind"]),
                         spans, reduced)
        for m in cell_metrics(spec, cell["name"], per_layer=True):
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    else:
        for m in cell_metrics(spec, cell["name"], per_layer=False):
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = load_module("e2e", m["name"]).compute(window)
            if value is None:
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   "nothing in this window")
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def check(name: str, value: float, limit: float) -> dict:
    """One compared number: passes when `value <= limit`."""
    value = float(value)
    return {"name": name, "value": value, "limit": float(limit),
            "ok": bool(value <= limit)}


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
              f"{verdict}", file=sys.stderr)
    sys.stderr.flush()

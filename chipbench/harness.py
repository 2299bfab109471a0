"""What every cell shares: finding a cell's files and its configuration's
architecture by name, the compile clock, per-layer metric readers, the
device record and the result line."""
from __future__ import annotations

import contextlib
import functools
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

# Seconds of the window a --trace 1 run profiles, its last ones, where a
# saturated engine has begun to replace finished requests: a
# serving second holds some 300k device operations, and reading them back
# takes about 30 microseconds each.
TRACE_SECONDS = 4.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@contextlib.contextmanager
def annotate(on: bool, name: str):
    """A host span in the profiler's trace, when the run is traced."""
    if not on:
        yield
        return
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"unknown workload {name!r}; known: "
                     f"{[w['name'] for w in bench['workloads']]}")


def load_json(kind: str, name: str) -> dict:
    return json.loads((HERE / kind / f"{name}.json").read_text())


def load_module(path: Path, name: str):
    """The Python file ``path``, loaded as the module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def _arch_module(path: Path):
    return load_module(path, "chipbench_arch_" + path.stem)


def arch(spec: dict):
    """The module ``arch/<arch>.py`` of the architecture the configuration
    names under ``"arch"``; raises SystemExit, naming the modules there,
    when the key is missing or names none of them."""
    known = sorted(p.stem for p in (HERE / "arch").glob("*.py"))
    name = spec.get("arch")
    if name not in known:
        raise SystemExit(f"configuration {spec.get('name')!r} names the "
                         f"architecture {name!r}; chipbench/arch has "
                         f"{known}")
    return _arch_module(HERE / "arch" / f"{name}.py")


def cell_files(name: str) -> tuple:
    """(benchmark, cell, configuration, traffic mix, correctness limits)
    of the cell ``name``, each found by the name ``BENCHMARK.json`` gives;
    the configuration's architecture is looked up here, before set-up."""
    bench = benchmark()
    cell = workload(bench, name)
    spec = load_json("configs", cell["config"])
    arch(spec)
    return (bench, cell, spec, load_json("traffic", cell["traffic"]),
            load_json("checks", cell["name"]))


def open_chip(cell: dict) -> dict:
    """Point JAX's persistent compilation cache at its directory, every
    program included however quick to compile, and return the device
    record (raises SystemExit without a TPU or with too few chips)."""
    # before the runtime starts: it would log to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return device_record(cell["chips"])


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"chipbench/peaks.json")
    return table[device_kind]


def end_to_end_names(bench: dict, cell: str) -> list:
    return [m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer_specs(bench: dict, cell: str) -> list:
    e2e = set(end_to_end_names(bench, cell))
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in e2e
                             else [])]


def read_per_layer(specs: list, ctx) -> dict:
    """Run each metric's reader (``metrics/<name>.py``, ``read(ctx)``);
    a reader that finds nothing returns None and the metric is left out."""
    out = {}
    for spec in specs:
        mod = load_module(HERE / "metrics" / f"{spec['name']}.py",
                          "chipbench_metric_"
                          + spec["name"].replace(".", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def read_trace(prof, bench: dict, cell: str, spec: dict, device: dict,
               result: dict, **ctx) -> None:
    """Fill a traced run's result: its per-layer metrics (each reader
    gets the reduced trace, the chip's peaks, the configuration's ``arch``
    module and its ``dims``, and ``ctx``), the device's busy and traced
    seconds, and the breakdown."""
    tr = prof.load()
    a = arch(spec)
    ctx = types.SimpleNamespace(trace=tr, peaks=peaks(device["kind"]),
                                arch=a, dims=a.dims(spec), **ctx)
    result["metrics"] = read_per_layer(per_layer_specs(bench, cell), ctx)
    device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
    result["breakdown"] = {
        "device_ops": [[n, s] for n, s in tr.top_ops(10)],
        "idle_gaps": [[n, float(s)] for n, s in tr.gaps(0, 10)]}


class CompileClock:
    """Backend compile seconds, compilations and persistent-cache hits of
    this process, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def device_record(chips: int) -> dict:
    """Platform, kind and count as JAX reports them; raises SystemExit
    when there is no TPU or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench needs a TPU; JAX found "
                         f"{devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def emit(result: dict, checks: dict) -> None:
    """Print the numbers compared beside their limits as the last lines of
    stderr, then the result line, with them under ``check``, last."""
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps({**result, "check": checks}), flush=True)


class Profile:
    """Profiles one span of the window, its last ``TRACE_SECONDS``,
    starting and stopping between steps, with the device drained first so
    that the span holds whole steps. The span is the host annotation
    ``window`` that ``reduce`` clips to."""

    def __init__(self, on: bool, seconds: float):
        self.on, self.active, self.dir = on, False, None
        self.host_span = (0.0, 0.0)      # the span on ``perf_counter``
        self.start = max(0.0, seconds - TRACE_SECONDS)
        self.stop = self.start + TRACE_SECONDS

    def tick(self, elapsed: float, drain) -> None:
        """Call between steps with the seconds since the window opened;
        ``drain()`` waits for the device."""
        import jax

        from chipbench import reduce

        if not self.on:
            return
        if self.dir is None and elapsed >= self.start:
            drain()
            self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # benchmark annotations only
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(reduce.WINDOW)
            self._ann.__enter__()
            self.active = True
            self.host_span = (time.perf_counter(), float("inf"))
        elif self.active and elapsed >= self.stop:
            self.close(drain)

    def close(self, drain) -> None:
        import jax

        if self.active:
            drain()
            self.host_span = (self.host_span[0], time.perf_counter())
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False

    def load(self):
        """The reduced trace; the trace files are deleted."""
        from chipbench import reduce

        try:
            return reduce.load(next(Path(self.dir).rglob("*.xplane.pb")))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

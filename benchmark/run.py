"""Run one benchmark cell once and print the contract's last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration in
``benchmark/configs/<config>.json``; its traffic mix in
``benchmark/traffic/<traffic>.json``, whose ``surface`` names the entry
point that drives it (``benchmark/surfaces/<surface>.py``); and each
per-layer metric in ``benchmark/metrics/<metric>.py``.  A new cell,
configuration, mix or metric is therefore a new file and a new entry.

Each run: require the chips the cell asks for (no accelerator: exit 2,
no result), set up from the seed, warm every shape the window uses,
measure for ``--seconds`` (with ``--trace 1`` under the profiler), free
the program's state, compare with the plain reference, print.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import sys
import time
import traceback

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class NoDevice(Exception):
    pass


def require_tpu(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: jax found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"{len(devs)} TPU chips, the cell needs {chips}")
    return devs[:chips]


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, ROOT).replace("/", "_") \
        .replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: str, workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, config, traffic), each found by name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no cell {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    with open(os.path.join(root, "benchmark", "configs",
                           cell["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, config, traffic


def metrics_for(bench: dict, cell: str, kind: str, reported=()) -> list:
    """The cell's end-to-end (``kind='end_to_end'``) or per-layer
    metrics: those that list the cell, or list no cells at all (a
    per-layer metric without a list goes wherever the metric it moves
    is reported)."""
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


class Window:
    """The measured window: a deadline, and under ``--trace 1`` the
    profiler with a ``bench.window`` span around it."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.start = self.end = None

    def done(self) -> bool:
        return time.perf_counter() - self.start >= self.seconds

    @property
    def elapsed(self) -> float:
        return self.end - self.start


class Context:
    """What a surface is handed: the seed and sizes, the window, spans,
    and the seam through which tests and the control replace the
    program's timed path (``under_test``)."""

    def __init__(self, args, cell, config, traffic, devices,
                 under_test=None):
        self.seed, self.seconds = args.seed, args.seconds
        self.trace = bool(args.trace)
        self.cell, self.config, self.traffic = cell, config, traffic
        self.devices = devices
        self.under_test = under_test or (lambda kind, obj: obj)
        self.setup_s = self.closed = None
        self.memory_peak_bytes = None
        self.trace_summary = None
        self.compiles_in_window = 0
        self._compiling = False
        # teardown a surface leaves, run in order by in-process callers
        # such as the tests; a real run exits without it
        self.finalizers = []
        self.notes = []

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")

    @contextlib.contextmanager
    def window(self):
        import jax

        from benchmark import trace as tr

        w = Window(self.seconds)
        tmp = None
        if self.trace:
            import tempfile

            tmp = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
        self._compiling = True
        self.setup_s = time.perf_counter() - T_START
        self.note(f"set-up: window opens at {self.setup_s:.3f} s")
        ann = self.span("window")
        ann.__enter__()
        w.start = time.perf_counter()
        try:
            yield w
        finally:
            w.end = self.closed = time.perf_counter()
            ann.__exit__(None, None, None)
            self._compiling = False
            if tmp is not None:
                jax.profiler.stop_trace()
                self.trace_summary = tr.summarize_dir(tmp)
                import shutil

                shutil.rmtree(tmp, ignore_errors=True)

    def on_compile(self, event: str, *a, **kw) -> None:
        """Backend compiles in the window, less those the persistent
        cache served (both events fire on a cache hit)."""
        if self._compiling:
            if event.endswith("backend_compile_duration"):
                self.compiles_in_window += 1
            elif event.endswith("compilation_cache/cache_hits"):
                self.compiles_in_window -= 1

    def note(self, msg: str) -> None:
        """A line for standard error, printed before the checks: what a
        failed comparison found."""
        self.notes.append(msg)

    def read_memory_peak(self) -> None:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        self.memory_peak_bytes = max(peaks) if peaks else None


class Result:
    """A surface's report: end-to-end values (host clock), the work and
    program counters the per-layer readers take, and every number the
    correctness check compared, each with its limit."""

    def __init__(self, *, e2e, attempted, failed, checks, work=None,
                 counters=None):
        self.e2e = e2e
        self.attempted, self.failed = attempted, failed
        self.checks = checks  # name -> (value, limit); value <= limit
        self.work = work or {}
        self.counters = counters or {}
        self.trace = None
        self.peaks = None

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.checks.values())


def configure_cache(root: str) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, whatever
    the environment says, and every program kept in it.  The variable is
    set too, so that program code which places the cache takes this one."""
    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def execute(args, *, root=ROOT, device_check=require_tpu, under_test=None,
            overrides=None, teardown_after=False) -> dict:
    """One run; returns the result line as a dict."""
    bench, cell, config, traffic = load_cell(root, args.workload)
    for key, val in (overrides or {}).items():
        (config if key in config else traffic)[key] = val
    devices = device_check(cell["chips"])
    t_devices = time.perf_counter() - T_START
    configure_cache(root)
    import jax.monitoring

    from benchmark import peaks

    ctx = Context(args, cell, config, traffic, devices, under_test)
    ctx.note(f"set-up: devices found at {t_devices:.3f} s")
    jax.monitoring.register_event_duration_secs_listener(ctx.on_compile)
    jax.monitoring.register_event_listener(ctx.on_compile)
    surface = load_module(os.path.join(root, "benchmark", "surfaces",
                                       traffic["surface"] + ".py"))
    try:
        res: Result = surface.run(ctx)
        ctx.note(f"after the window: {time.perf_counter() - ctx.closed:.3f} s"
                 " to the end of the check")
    finally:
        if teardown_after:
            for fn in ctx.finalizers:
                fn()
    if ctx.memory_peak_bytes is None:
        ctx.read_memory_peak()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    e2e = metrics_for(bench, cell["name"], "end_to_end")
    values = {**res.e2e, "setup_s": ctx.setup_s}
    line: dict = {"correct": res.correct, "attempted": res.attempted,
                  "failed": res.failed}
    if not args.trace:
        line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                       "unit": m["unit"]} for m in e2e}
    else:
        res.trace = ctx.trace_summary
        res.peaks = peaks.peaks(d0.device_kind) \
            if d0.platform == "tpu" else None
        line["metrics"] = {}
        for m in metrics_for(bench, cell["name"], "per_layer",
                             [x["name"] for x in e2e]):
            reader = load_module(os.path.join(root, "benchmark", "metrics",
                                              m["name"] + ".py"))
            v = reader.read(res)
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        t = ctx.trace_summary
        device["busy_s"], device["window_s"] = t["busy_s"], t["window_s"]
        line["breakdown"] = {"device_ops": t["device_ops"][:10],
                             "idle_gaps": t["idle_gaps"][:10]}
    line["notes"] = ctx.notes[:20]
    line["device"] = device
    line["compiles_in_window"] = ctx.compiles_in_window
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in res.checks.items()}
    return line


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, **kw) -> int:
    args = parse_args(argv)
    try:
        line = execute(args, **kw)
    except NoDevice as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    except Exception:
        traceback.print_exc()
        print("benchmark: run failed", file=sys.stderr, flush=True)
        return 1
    for note in line.pop("notes"):
        print(f"note: {note}", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # no teardown: a served cell's in-process cluster would take ~13 s
    # to stop, paid by every run; the process's exit frees it all
    os._exit(rc)

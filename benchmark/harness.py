"""The harness: one cell, one process, one result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found from ``BENCHMARK.json`` by
name, so a later PR adds a cell by adding files and edits none:

* ``<configs dir>/<config>.json``          the sizes as run, ``limits``, ``assumed``
* ``<configs dir>/<config>.driver.py``     prepare / call / finalize through the program
* ``<configs dir>/<config>.reference.py``  the plain reference
* ``<configs dir>/<config>.work.py``       algorithmic operations and bytes
* ``<paths[0]>/workloads/<cell>.json``     the traffic: generator parameters,
  ``epochs_per_call``, ``target``, ``max_epochs``
* ``<paths[0]>/metrics/<metric>.py``       one per-layer reader: ``read(ctx)``
* ``<paths[0]>/peaks.json``                the chips' published peaks

(``<configs dir>`` is the directory of the configuration's ``file``.)

A run: make the data from ``--seed``; ``prepare`` through the program; drive
the one model object through its first three training calls (this compiles,
and is what the reference is compared with); then the window: back-to-back
*jobs*, each from the seed's first model until the program's own per-epoch
quality meets the cell's target; then read the device's memory, free the
program's state, follow the same three calls with the plain reference and
compare. ``PERF.md`` section 2 defines the end-to-end metrics.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmark import compare, trace_reduce, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# a traced window is cut to this length: the trace of a full window is large,
# and reading it has to fit the run's time limit
TRACE_SECONDS = 6.0
FIRST_CALLS = 3
SPANS = ("call", "fetch_quality", "job_reset")


# --------------------------------------------------------------------------- #
# finding the cell's files
# --------------------------------------------------------------------------- #

def load_module(path: str):
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in path)
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration's file
    traffic: dict           # the cell's traffic file
    manifest: dict
    config_dir: str
    bench_dir: str          # the benchmark's own directory: paths[0]

    @property
    def limits(self) -> dict:
        """The limits of ``correct``: the configuration's, unless the cell's
        traffic file states its own (a cell on another mesh reads other gaps)."""
        return self.traffic.get("limits", self.config["limits"])

    def part(self, kind: str):
        """The configuration's ``driver``, ``reference`` or ``work`` module."""
        return load_module(os.path.join(
            self.config_dir, f"{self.config_name}.{kind}.py"))

    def metrics(self, group: str) -> List[dict]:
        """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]


def find_cell(name: str, root: str = ROOT) -> Cell:
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}")
    config = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    config_file = os.path.join(root, config["file"])
    bench_dir = os.path.join(root, manifest["paths"][0])
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=config["name"],
        config=load_json(config_file),
        traffic=load_json(os.path.join(bench_dir, "workloads", name + ".json")),
        manifest=manifest, config_dir=os.path.dirname(config_file),
        bench_dir=bench_dir)


def peak_of(bench_dir: str, device_kind: str) -> dict:
    """The chip's published peaks; a device that is not listed is an error."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"peaks.json has no device {device_kind!r}")
    return table[device_kind]


# --------------------------------------------------------------------------- #
# counters and spans the harness keeps itself
# --------------------------------------------------------------------------- #

class CompileCounter:
    """Backend compile seconds and persistent-cache hits and misses of this
    process, from jax's monitoring events (as ``chip_smoke.py`` reads them)."""

    def __init__(self):
        import jax.monitoring as mon

        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1


class Spans:
    """Host spans on the harness's clock; inside a traced window they are
    also written into the profiler's trace under the same names."""

    def __init__(self):
        self.records: List[tuple] = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        note = contextlib.nullcontext()
        if self.annotate:
            import jax

            note = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with note:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def seconds(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.records if n == name)


# --------------------------------------------------------------------------- #
# the first calls and the window
# --------------------------------------------------------------------------- #

def first_calls(driver, span: Spans) -> dict:
    """Drive the model through its first training calls from the seed's
    first model, by the window's own call. Returns the program's record for
    the comparison (``compare.numbers``); the fetch of the model goes through
    the program's own way out."""
    record: Dict[str, object] = {"quality": []}
    with span("first_calls"):
        state = driver.initial()
        for i in range(1, FIRST_CALLS + 1):
            state, quality = driver.call(state)
            record["quality"] += list(np.asarray(quality, np.float64))
            if i in (1, FIRST_CALLS):
                record[f"after_{i}"] = driver.finalize(state)
    return record


@dataclasses.dataclass
class Window:
    start: float
    end: float
    call_s: List[float]
    jobs: List[tuple]              # (end time, epochs to target), finished
    failed: int
    epochs: int

    @property
    def seconds(self) -> float:
        return self.end - self.start


def run_window(driver, cell_traffic: dict, seconds: float, span: Spans,
               clock: Callable[[], float] = time.perf_counter) -> Window:
    """Back-to-back jobs for ``seconds``: the call that is running when the
    time is up is finished and counted, and the window always sees one job to
    its verdict. Every epoch of every call counts, also those of a job the
    window's end cut short."""
    per_call = int(cell_traffic["epochs_per_call"])
    max_epochs = int(cell_traffic["max_epochs"])
    target = float(cell_traffic["target"]["at_most"])
    scale = float(driver.quality_scale)
    win = Window(clock(), 0.0, [], [], 0, 0)
    deadline = win.start + seconds
    now = win.start
    while now < deadline or not (win.jobs or win.failed):
        with span("job_reset"):
            state, epochs = driver.initial(), 0
        while True:
            t0 = clock()
            with span("call"):
                state, quality = driver.call(state)
            with span("fetch_quality"):
                quality = np.asarray(quality, np.float64)
            now = clock()
            win.call_s.append(now - t0)
            epochs += per_call
            win.epochs += per_call
            met = np.flatnonzero(quality * scale <= target)
            if met.size:
                win.jobs.append((now, epochs - per_call + int(met[0]) + 1))
                break
            if epochs >= max_epochs:
                win.failed += 1
                break
            if now >= deadline and (win.jobs or win.failed):
                break
    win.end = now
    return win


def end_to_end(win: Window, samples_per_epoch: int, setup_s: float) -> dict:
    out = {
        "samples_per_s": win.epochs * samples_per_epoch / win.seconds,
        "call_ms_p95": 1e3 * float(np.percentile(win.call_s, 95)),
        "setup_s": setup_s,
    }
    if win.jobs:
        out["time_to_target_s"] = (win.jobs[-1][0] - win.start) / len(win.jobs)
    return out


# --------------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class Context:
    """What a per-layer reader (``benchmark/metrics/<name>.py``) may read."""
    cell: Cell
    trace: Optional[trace_reduce.Summary]
    window: Window
    spans: Spans
    counters: dict
    work: dict
    device: dict
    peak: Callable[[], dict]


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_bytes(chips: int, key: str) -> Optional[int]:
    """``memory_stats()[key]`` of the fullest of the chips the cell uses."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()[:chips]]
    values = [s[key] for s in stats if s and key in s]
    return int(max(values)) if values else None


# the TPU runtime's threads on the path from "the device is done" to "the
# next program is enqueued" (names as /proc/<pid>/task/<tid>/comm cuts them)
_RUNTIME_THREADS = ("EventFD", "futex-default", "pjrt-tpu-tasks",
                    "tfrt-non-block")


@contextlib.contextmanager
def steady_threads():
    """Keep the calling thread on a core of its own for the window, and the
    runtime's launch-path threads on the others.

    Measured on the v5e host (PR 24, ``PERF.md`` section 6): where the OS lets
    the thread that drives the calls share a core with one of those threads,
    every call of a program that holds a collective takes 2.8 ms longer, for
    the life of the process: a quarter of all processes, the device's time
    identical. Ten of ten processes placed like this ran in the fast mode.
    Does nothing where the platform has no thread affinity."""
    if not hasattr(os, "sched_setaffinity"):
        yield
        return
    before = {}
    cores = sorted(os.sched_getaffinity(0))
    try:
        if len(cores) > 1:
            others = cores[1:]
            placed = 0
            for tid in sorted(int(t) for t in os.listdir("/proc/self/task")):
                try:
                    with open(f"/proc/self/task/{tid}/comm") as fh:
                        name = fh.read().strip()
                    if name.startswith(_RUNTIME_THREADS):
                        before[tid] = os.sched_getaffinity(tid)
                        os.sched_setaffinity(
                            tid, {others[placed % len(others)]})
                        placed += 1
                except OSError:           # the thread ended meanwhile
                    continue
            before[0] = os.sched_getaffinity(0)
            os.sched_setaffinity(0, {cores[0]})
        yield
    finally:
        for tid, mask in before.items():
            try:
                os.sched_setaffinity(tid, mask)
            except OSError:
                pass


def capture_trace(run: Callable[[], Window], span: Spans, name: str):
    """Run the window under the profiler; ``(window, trace summary)``."""
    import jax

    out_dir = os.path.join(TRACE_DIR, name)
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir)
    span.annotate = True
    try:
        with span("window"):
            win = run()
    finally:
        span.annotate = False
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    summary = trace_reduce.reduce(files[0], spans=SPANS) if files else None
    shutil.rmtree(out_dir, ignore_errors=True)
    return win, summary


def describe_step(driver) -> dict:
    """What ran: the layout the program chose, whether its compiled step
    holds a Pallas kernel (``tpu_custom_call``), and the scratch the step
    holds on each chip while it runs."""
    layout, step = driver.compiled_step()
    stats = step.memory_analysis()
    return {"layout": layout,
            "tpu_custom_call": "tpu_custom_call" in step.as_text(),
            "step_temp_bytes": int(
                getattr(stats, "temp_size_in_bytes", 0) or 0)}


def make_data(cell: Cell, seed: int) -> dict:
    data = traffic.generate(cell.traffic, cell.config, seed)
    data["init_seed"] = int(seed)       # the seed of the program's first model
    return data


def follow_reference(cell: Cell, data: dict, products=None) -> tuple:
    """The plain reference over the same first calls: ``(first model,
    record)``. ``products`` makes the lower-precision control of it."""
    ref = cell.part("reference").Reference(cell.config, data, cell.chips)
    first = state = ref.initial()
    per_call = int(cell.traffic["epochs_per_call"])
    record: Dict[str, object] = {"quality": []}
    for i in range(1, FIRST_CALLS + 1):
        state, quality = ref.advance(state, per_call, products=products)
        record["quality"] += list(quality)
        if i in (1, FIRST_CALLS):
            record[f"after_{i}"] = state
    ref.free()
    return first, record


def open_cell(name: str, root: str = ROOT, require_accelerator: bool = True,
              log=sys.stderr) -> tuple:
    """Find the cell, see that the machine holds the chips it asks for, and
    put the process into the state the configuration states (compile cache,
    matmul precision). ``(cell, device, compile cache directory)``."""
    cell = find_cell(name, root)
    import jax

    from harp_tpu.aot import cache

    device = device_info()
    if device["count"] < cell.chips or (
            require_accelerator and device["platform"] != "tpu"):
        print(f"benchmark: {name} needs {cell.chips} TPU chip(s); jax reports "
              f"{device['count']} x {device['platform']}", file=log)
        raise SystemExit(3)
    cache_dir = cache.enable_compile_cache()
    precision = cell.config.get("jax_default_matmul_precision")
    if precision:
        jax.config.update("jax_default_matmul_precision", precision)
    return cell, device, cache_dir


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_accelerator: bool = True, root: str = ROOT,
             log=sys.stderr, t_process: Optional[float] = None) -> dict:
    """One run of one cell; returns the result line as a dict. Raises
    ``SystemExit`` with a code other than 0 where the run cannot be made."""
    t_process = time.perf_counter() if t_process is None else t_process
    cell, device, cache_dir = open_cell(name, root, require_accelerator, log)
    compiles = CompileCounter()
    span = Spans()

    with span("data_gen"):
        data = make_data(cell, seed)
    driver = cell.part("driver").Driver(
        cell.config, cell.traffic, data, cell.chips)
    with span("prepare"):
        driver.prepare()
    program = first_calls(driver, span)
    with span("describe"):
        described = describe_step(driver)
    setup_s = time.perf_counter() - t_process
    print(json.dumps({"workload": name, **device, "chips_used": cell.chips,
                      "compile_cache_dir": cache_dir, **described}))
    sys.stdout.flush()

    compiles_before, setup_compile_s = compiles.compiles, compiles.compile_s

    def window() -> Window:
        return run_window(driver, cell.traffic,
                          min(seconds, TRACE_SECONDS) if trace else seconds,
                          span)

    summary = None
    gc.collect()
    gc.disable()          # no collector pause inside the measured window
    try:
        with steady_threads():
            if trace:
                win, summary = capture_trace(window, span, name)
            else:
                win = window()
    finally:
        gc.enable()
    compiles_in_window = compiles.compiles - compiles_before

    # the runtime's peak counts live buffers and not the scratch a running
    # program holds (ml10m: 1.67 GB against 3.28 GB of scratch, PERF.md
    # section 4), so the chip's peak is their sum
    buffers_peak = memory_bytes(cell.chips, "peak_bytes_in_use")
    resident_bytes = memory_bytes(cell.chips, "bytes_in_use")
    step_temp = described["step_temp_bytes"]
    peak_bytes = None if buffers_peak is None else buffers_peak + step_temp
    samples_per_epoch = driver.samples_per_epoch
    driver.free()
    del driver
    gc.collect()

    with span("reference"):
        first, reference = follow_reference(cell, data)
    read = compare.numbers(first, program, reference)
    correct, compared = compare.verdict(read, cell.limits)
    if compiles_in_window:
        print(f"benchmark: {compiles_in_window} compilation(s) inside the "
              "measured window", file=log)

    result = {"correct": bool(correct),
              "attempted": len(win.jobs) + win.failed, "failed": win.failed}
    e2e = end_to_end(win, samples_per_epoch, setup_s)
    if trace:
        ctx = Context(
            cell=cell, trace=summary, window=win, spans=span,
            counters={"backend_compile_s": setup_compile_s,
                      "cache_hits": compiles.hits,
                      "cache_misses": compiles.misses,
                      "peak_bytes": peak_bytes,
                      "samples_per_epoch": samples_per_epoch,
                      "samples_per_s": e2e["samples_per_s"]},
            work=cell.part("work").work(cell.config, cell.traffic),
            device=device,
            peak=lambda: peak_of(cell.bench_dir, device["kind"]))
        wanted, values = cell.metrics("per_layer"), {}
        for metric in wanted:
            reader = load_module(os.path.join(
                cell.bench_dir, "metrics", metric["name"] + ".py"))
            value = reader.read(ctx)
            if value is not None:
                values[metric["name"]] = float(value)
    else:
        wanted, values = cell.metrics("end_to_end"), e2e
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in wanted if m["name"] in values}
    result["device"] = {**device, "memory_peak_bytes": peak_bytes}
    if trace and summary is not None:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = {
            "device_ops": [list(x) for x in summary.device_ops[:10]],
            "idle_gaps": [list(x) for x in summary.idle_gaps[:10]]}
    result["window"] = {
        "seconds": win.seconds, "calls": len(win.call_s),
        "call_ms_median": 1e3 * float(np.median(win.call_s)),
        "call_ms_max": 1e3 * max(win.call_s),
        # the three longest calls, as [index, ms]: where a stall fell
        "call_ms_top": [[int(i), 1e3 * win.call_s[i]]
                        for i in np.argsort(win.call_s)[:-4:-1]],
        "epochs": win.epochs, "jobs_finished": len(win.jobs),
        "epochs_to_target": sorted({e for _, e in win.jobs}),
        "memory_resident_bytes": resident_bytes,
        "memory_buffers_peak_bytes": buffers_peak,
        "memory_step_temp_bytes": step_temp,
        "compiles_in_window": compiles_in_window,
        "data_gen_s": span.seconds("data_gen"),
        "prepare_s": span.seconds("prepare"),
        "first_calls_s": span.seconds("first_calls"),
        "describe_s": span.seconds("describe"),
        "reference_s": span.seconds("reference")}
    result["compared"] = {
        k: {"value": min(v["value"], 1e30), "limit": v["limit"]}
        for k, v in compared.items()}
    for k, v in result["compared"].items():
        held = "not held" if v["limit"] is None else f"limit {v['limit']:.3g}"
        print(f"compared {k} {v['value']:.6g} {held}", file=log)
    print(f"correct {result['correct']}", file=log)
    return result

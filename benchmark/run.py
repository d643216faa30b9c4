#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted as `setup_s`): device check, data made on the device from
the seed, one warm-up call of the cell's own job. Window: the job call is
repeated until `--seconds` have passed, ending on a call boundary. Then
the peak memory is read, and the cell's plain reference decides `correct`
on what the window's last call returned. `--trace 1` wraps the window in a
`jax.profiler` trace and reports the per-layer metrics read from it.

Nothing here names a cell, a configuration or a metric: the cell's
configuration, traffic, family and per-layer readers are files found by
the names in BENCHMARK.json (see README.md).
"""

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402

CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Refused(Exception):
    """The run cannot be made as asked; exit non-zero, print no result."""


def load(kind: str, name: str):
    """The module `benchmark/<kind>/<name>.py`, found by name."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise Refused(f"no {kind}/{name}.py under {BENCH}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def find_cell(manifest, name: str):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = read_json(os.path.join(ROOT, entry["file"]))
    traffic = read_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def open_cell(workload: str, rehearse: bool):
    """(manifest, cell, configuration, traffic, family) of a workload; a
    rehearsal runs at the configuration's `rehearsal` sizes."""
    manifest = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = find_cell(manifest, workload)
    if rehearse:
        config = {**config, **config.get("rehearsal", {})}
    family = load("families", config["family"])
    for module in family.PROGRAM_MODULES:
        if importlib.util.find_spec(module) is None:
            raise Refused(f"the program ({module}) is not in this checkout")
    return manifest, cell, config, traffic, family


def metrics_of(manifest, section: str, cell_name: str):
    return [m for m in manifest[section]
            if cell_name in m.get("workloads", [cell_name])]


def place_compile_cache(jax):
    """One fixed directory inside the checkout, unless the environment
    places it; every program is kept, however fast it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def check_devices(jax, chips: int, rehearse: bool):
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    peaks = read_json(os.path.join(BENCH, "peaks.json"))["by_device_kind"]
    if rehearse:
        if platform == "tpu":
            raise Refused("--rehearse is for a machine without the chip")
    elif platform != "tpu":
        raise Refused(f"no accelerator: jax found platform {platform!r}")
    elif kind not in peaks:
        raise Refused(f"device kind {kind!r} is not in peaks.json")
    if len(devices) != chips:
        raise Refused(f"the cell asks for {chips} chip(s), "
                      f"jax found {len(devices)}")
    return devices, peaks.get(kind)


class CompileCounter:
    """Programs built while `armed`, as jax's own events report them:
    `compiles` went to the compiler (a miss of the persistent cache, which
    every program goes through here), `loads` were traced and lowered
    anew and then read back from the cache."""

    def __init__(self, jax):
        self.compiles, self.loads, self.armed = 0, 0, False
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if self.armed:
            self.compiles += event == CACHE_MISS_EVENT
            self.loads += event == CACHE_HIT_EVENT


def run_window(jax, call, seconds: float):
    """Repeat the job call until `seconds` have passed; the window runs
    from the first call's start to the last call's end. Returns the last
    result and [(start, end)] of every call on the host's clock."""
    spans, result = [], None
    with jax.profiler.TraceAnnotation("bench:window"):
        t0 = time.perf_counter()
        while True:
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:call"):
                result = call()
            b = time.perf_counter()
            spans.append((a - t0, b - t0))
            if b - t0 >= seconds:
                break
    return result, spans


def printable(value: float) -> float:
    """JSON has no infinity: a number that could not be read prints as
    one no limit admits."""
    return value if math.isfinite(value) else 1e300


def memory_peaks(devices):
    """Peak bytes of each device: the allocator's live buffers at their
    peak plus what the runtime reserved for the programs' temporaries. On
    this TPU runtime `peak_bytes_in_use` leaves a program's temporaries
    out (a step whose saved activations alone are gigabytes read 0.2 GB),
    and `peak_bytes_reserved` is the pool they live in."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"memory_stats device {d.id} {json.dumps(stats)}",
              file=sys.stderr)
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return peaks


def traced_window(jax, call, seconds: float):
    """The window inside a profiler trace; returns the window's results
    and the reduced trace. The trace goes under TMPDIR and is removed."""
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            result, spans = run_window(jax, call, seconds)
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if len(files) != 1:
            raise Refused(f"expected one trace file, found {files}")
        return result, spans, trace_reduce.reduce_file(files[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def layer_metrics(manifest, cell_name: str, context):
    """Every per-layer metric of the cell, by its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics_of(manifest, "per_layer", cell_name):
        value = load("layer_metrics", m["name"]).read(context)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(context, top: int = 10):
    dev = context["trace"].device(context["fullest_device"])
    ops = trace_reduce.op_sums(dev.ops)
    idle = trace_reduce.gaps(dev.busy, context["trace"].window_s)
    return {"device_ops": [[k, v] for k, v in list(ops.items())[:top]],
            "idle_gaps": trace_reduce.label_gaps(idle, context["trace"].host,
                                                 top)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on a machine without the chip, at the sizes "
                         "under the configuration's `rehearsal` key; "
                         "prints no device metric")
    args = ap.parse_args(argv)

    manifest, cell, config, traffic, family = open_cell(args.workload,
                                                        args.rehearse)

    phases = {"start": time.perf_counter() - _PROCESS_START}
    import jax
    place_compile_cache(jax)
    phases["import_jax"] = time.perf_counter() - _PROCESS_START
    devices, peak = check_devices(jax, cell["chips"], args.rehearse)
    phases["devices"] = time.perf_counter() - _PROCESS_START
    compiles = CompileCounter(jax)
    job_seed = args.seed % (2 ** 31 - 1)

    data = jax.block_until_ready(
        family.make_data(config, args.seed, cell["chips"]))
    phases["data"] = time.perf_counter() - _PROCESS_START
    call = family.make_call(config, traffic, data, job_seed)
    with jax.profiler.TraceAnnotation("bench:warmup"):
        call()
    setup_s = time.perf_counter() - _PROCESS_START
    print("setup reached (s from process start): "
          + " ".join(f"{k} {v:.2f}" for k, v in phases.items())
          + f" warmup {setup_s:.2f}", file=sys.stderr)

    compiles.armed = True
    if args.trace:
        result, spans, trace = traced_window(jax, call, args.seconds)
    else:
        result, spans = run_window(jax, call, args.seconds)
        trace = None
    compiles.armed = False
    wall_s = spans[-1][1] - spans[0][0]
    got = family.outputs(result)
    del result
    peaks_bytes = memory_peaks(devices)

    checks = family.check(config, traffic, data, job_seed, got)["checks"]
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(peaks_bytes)}
    line = {"correct": correct, "attempted": len(spans), "failed": 0}
    if args.rehearse:
        line["metrics"] = {}
    elif args.trace:
        context = {
            "trace": trace, "call_spans": spans, "wall_s": wall_s,
            "steps": len(spans) * traffic["steps_per_call"],
            "window_compiles": compiles.compiles,
            "window_program_loads": compiles.loads, "config": config,
            "traffic": traffic, "chips": cell["chips"], "peak": peak,
            "work": load("work", config["family"]),
            "fullest_device": devices[peaks_bytes.index(
                max(peaks_bytes))].id}
        line["metrics"] = layer_metrics(manifest, cell["name"], context)
        device["busy_s"] = sum(d.busy_s for d in trace.devices) \
            / len(trace.devices)
        device["window_s"] = trace.window_s
        line["breakdown"] = breakdown(context)
    else:
        values = {family.RATE_METRIC:
                  family.units_per_call(config, traffic) * len(spans)
                  / wall_s,
                  "setup_s": setup_s}
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(manifest, "end_to_end", cell["name"])}
    line["device"] = device
    line["checks"] = {c["name"]: {"value": printable(c["value"]),
                                  "limit": c["limit"]} for c in checks}

    for c in checks:
        print(f"check {c['name']} value {printable(c['value']):.6g} limit "
              f"{c['limit']:.6g}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Refused as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        sys.exit(2)

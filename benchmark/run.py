#!/usr/bin/env python3
"""One cell of the benchmark, once, in one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

reads the cell from ``BENCHMARK.json``, its configuration from
``benchmark/configs/<config>.json`` (and the plain reference beside it,
``benchmark/reference/<config>.py``), its traffic from
``benchmark/traffic/<traffic>.json`` and hands them to the runner the
configuration names (``benchmark/runners/<runner>.py``).  The runner builds
the system with weights made on the device from the seed, warms up, compares
with the reference, measures for ``--seconds`` and returns what it saw; this
file prints it as the contract's last line.  With ``--trace 1`` a few seconds
of the window are traced and each per-layer metric's own reader
(``benchmark/metrics/<name>.json`` names it, ``benchmark/readers/`` holds it)
takes its number from the trace, the spans and the counters.

No TPU, fewer chips than the cell asks for, or a ``device_kind`` that
``benchmark/peaks.json`` does not know, is an error.  ``--rehearse`` is the
benchmark's own switch for the CPU rehearsal (tiny sizes from the
configuration's ``rehearsal`` block, interpreted kernels): the driver's
command never passes it and its last line says ``"platform": "cpu"``.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

T_START = time.perf_counter()            # process start, as near as we get

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)                 # the program under test
sys.path.insert(0, HERE)                 # the benchmark's own modules
sys.path.insert(0, os.path.join(HERE, "reference"))
sys.path.insert(0, os.path.join(HERE, "readers"))


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name):
    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    return manifest, cells[name]


def metric_applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


class Setup:
    """Set-up time in named parts; ``total()`` runs from process start."""

    def __init__(self):
        self.parts = {}
        self._t = T_START

    def mark(self, name):
        now = time.perf_counter()
        self.parts[name] = self.parts.get(name, 0.0) + now - self._t
        self._t = now

    def total(self):
        return time.perf_counter() - T_START


class CompileCounter:
    """Counts the programs jax asked its compile cache for (a hit loads, a
    miss compiles: either way a shape met for the first time), over the run
    and inside the measured window."""

    def __init__(self):
        self.hits = self.misses = self.in_window = 0
        self.window_open = False

    def install(self):
        import jax

        def on_event(name, **_):
            if name.endswith("/cache_hits"):
                self.hits += 1
            elif name.endswith("/cache_misses"):
                self.misses += 1
            else:
                return
            if self.window_open:
                self.in_window += 1
        jax.monitoring.register_event_listener(on_event)


class WindowTrace:
    """Traces at most ``length_s`` seconds of the window.  The stretch starts
    ``start_s`` into the window by the clock, or, where the runner gives a
    ``progress`` callable (a closed list: the share of its work the engine
    has scheduled) and the mix a ``start_share``, as soon as ``progress()``
    reaches that share: a stretch of the work, wherever a faster or slower
    tree puts it on the clock (and at ``latest_start_s`` at the latest, so
    that a window cut short still holds a trace).  ``poll()`` from the
    measuring loop, or ``run_in_thread()`` where the loop is inside the
    program."""

    def __init__(self, enabled, out_dir, start_s, length_s, start_share=None,
                 latest_start_s=None):
        self.dir = out_dir
        self.start_s, self.length_s = start_s, length_s
        self.start_share, self.latest_start_s = start_share, latest_start_s
        self.progress = None               # the runner's, where it has one
        self.state = "idle" if enabled else "off"
        self.t0 = None
        self._span = None
        self._thread = None
        self._closing = False
        self.started_at = None
        self.placed = {}                   # where the stretch fell, for the log

    def open(self, t0):
        self.t0 = t0

    def by_share(self):
        return self.progress is not None and self.start_share is not None

    def _note(self, end):
        self.placed[f"{end}_s"] = time.perf_counter() - self.t0
        if self.progress is not None:
            self.placed[f"progress_at_{end}"] = self.progress()

    def _start(self):
        import shutil

        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation("bench_trace_window")
        self._span.__enter__()
        self.started_at = time.perf_counter()
        self.state = "tracing"
        self._note("trace_start")

    def _stop(self):
        import jax
        self._note("trace_stop")
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def _due(self, dt):
        if not self.by_share():
            return dt >= self.start_s
        return (self.progress() >= self.start_share
                or (self.latest_start_s is not None
                    and dt >= self.latest_start_s))

    def poll(self, before_stop=None):
        if self.state in ("off", "done"):
            return
        now = time.perf_counter()
        if self.state == "idle" and self._due(now - self.t0):
            self._start()
        elif (self.state == "tracing"
              and now - self.started_at >= self.length_s):
            if before_stop is not None:
                before_stop()
            self._stop()

    def run_in_thread(self):
        import threading
        if self.state == "off":
            return

        def bench_trace_poll_thread():     # xtrace drops it by this name
            while self.state != "done":
                if self._closing:          # the window outran the trace
                    if self.state == "tracing":
                        self._stop()
                    self.state = "done"
                    break
                self.poll()
                time.sleep(0.02)
        self._thread = threading.Thread(target=bench_trace_poll_thread,
                                        name="bench-trace")
        self._thread.start()

    def close(self):
        """Ends a trace the window outran; joins the thread."""
        self._closing = True
        if self._thread is not None:
            self._thread.join()
        elif self.state == "tracing":
            self._stop()

    def load(self):
        import xtrace
        path = xtrace.find_xplane(self.dir) if self.started_at else None
        return xtrace.load(path) if path else None


def device_block(devices, chips, rehearse, peaks):
    d0 = devices[0]
    if not rehearse:
        if d0.platform != "tpu":
            raise SystemExit(f"no TPU: jax reports platform "
                             f"{d0.platform!r}; the benchmark never falls "
                             f"back (use --rehearse for the CPU rehearsal)")
        if d0.device_kind not in peaks:
            raise SystemExit(f"device kind {d0.device_kind!r} is not in "
                             f"benchmark/peaks.json: add it with its source")
    if len(devices) < chips:
        raise SystemExit(f"cell asks for {chips} chips, jax has "
                         f"{len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def memory_peak(devices):
    """Peak bytes on the fullest chip: what the allocator handed out at its
    highest, plus the scratch it reserved for the largest program (the
    program's temporaries, which ``peak_bytes_in_use`` leaves out: on the
    v5e the serving engine showed 10.76 GB in use and 3.77 GB reserved)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else 0


def read_per_layer(manifest, cell, ctx):
    """Each per-layer metric of this cell through its own reader."""
    out = {}
    readers = {}
    for m in manifest["per_layer"]:
        if not metric_applies(m, cell["name"]):
            continue
        spec = load_json(HERE, "metrics", f"{m['name']}.json")
        rname = spec["reader"]
        if rname not in readers:
            readers[rname] = load_module(
                os.path.join(HERE, "readers", f"{rname}.py"),
                f"bench_reader_{rname}")
        value = readers[rname].read(ctx, spec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the configuration's tiny preset")
    ap.add_argument("--set", action="append", default=[],
                    metavar="PATH=JSON", help="override one key of the "
                    "traffic file for a sweep, e.g. arrivals.rate_per_s=3; "
                    "the driver's command never passes it")
    args = ap.parse_args(argv)

    setup = Setup()
    manifest, cell = load_cell(args.workload)
    seconds = (float(manifest["run_seconds"]) if args.seconds is None
               else args.seconds)
    config = load_json(ROOT, {c["name"]: c for c in manifest["configs"]}[
        cell["config"]]["file"])
    import traffic
    mix = traffic.load_mix(cell["traffic"])
    for item in args.set:
        path, _, raw = item.partition("=")
        node = mix
        *parents, leaf = path.split(".")
        for p in parents:
            node = node[p]
        node[leaf] = json.loads(raw)
    if args.rehearse:
        config = {**config, **config.get("rehearsal", {}),
                  "run": {**config["run"], **config.get("rehearsal", {}).get(
                      "run", {})}}
        mix = {**mix, **mix.get("rehearsal", {})}
        # the benchmark's switch, not the program's: interpreted kernels and
        # as many virtual CPU devices as the cell has chips
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={cell['chips']}")
    peaks = load_json(HERE, "peaks.json")
    reference = load_module(
        os.path.join(ROOT, config["reference"]),
        "bench_reference_" + "".join(c if c.isalnum() else "_"
                                     for c in cell["config"]))

    # the program's own helper places the compile cache: where
    # JAX_COMPILATION_CACHE_DIR says, else the fixed <checkout>/.jax_cache
    from deepspeed_tpu.runtime.resilience import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    compiles = CompileCounter()
    compiles.install()
    import jax
    setup.mark("imports")
    devices = jax.devices()
    device = device_block(devices, cell["chips"], args.rehearse, peaks)
    devices = devices[:cell["chips"]]
    print(json.dumps({"phase": "device", **device,
                      "compile_cache_dir": cache_dir, "seed": args.seed,
                      "seconds": seconds, "trace": args.trace,
                      "rehearsal": args.rehearse}), flush=True)

    trace_cfg = mix.get("trace", {})
    length = min(float(trace_cfg.get("length_s", 3.0)), seconds / 2)
    start = min(float(trace_cfg.get("start_s", seconds / 3)),
                seconds - length)
    tracer = WindowTrace(bool(args.trace),
                         os.path.join(ROOT, "benchmark_out", "trace",
                                      cell["name"]), start, length,
                         start_share=trace_cfg.get("start_share"),
                         latest_start_s=seconds - length)
    runner = load_module(
        os.path.join(HERE, "runners", f"{config['run']['runner']}.py"),
        f"bench_runner_{config['run']['runner']}")
    ctx = {"args": args, "cell": cell, "config": config, "mix": mix,
           "seconds": seconds, "devices": devices, "reference": reference,
           "peaks": peaks.get(device["kind"]), "setup": setup,
           "compiles": compiles, "tracer": tracer, "manifest": manifest,
           "rehearse": args.rehearse}
    result = runner.run(ctx)
    ctx.update(result)

    device["memory_peak_bytes"] = memory_peak(devices)
    print(json.dumps({"phase": "memory", "stats": {
        k: v for k, v in (devices[0].memory_stats() or {}).items()
        if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                 "largest_alloc_size", "bytes_reserved",
                 "peak_bytes_reserved")}}), flush=True)
    ctx["memory_peak_bytes"] = device["memory_peak_bytes"]
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.trace:
        trace = tracer.load()
        ctx["trace"] = trace
        if trace is not None:
            import xtrace
            lo, hi = xtrace.window_of(trace)
            ctx["trace_window"] = (lo, hi)
            device["busy_s"] = xtrace.busy_seconds(trace, lo, hi)
            device["window_s"] = (hi - lo) / 1e9
            line["breakdown"] = xtrace.breakdown(trace, lo, hi)
        line["metrics"] = read_per_layer(manifest, cell, ctx)
    else:
        line["metrics"] = {
            m["name"]: {"value": float(result["end_to_end"][m["name"]]),
                        "unit": m["unit"]}
            for m in manifest["end_to_end"]
            if metric_applies(m, cell["name"])
            and m["name"] in result["end_to_end"]}
    print(json.dumps({"phase": "setup", "setup_s": result["setup_s"],
                      "parts_s": setup.parts,
                      "compile_cache": {"hits": compiles.hits,
                                        "misses": compiles.misses,
                                        "in_window": compiles.in_window}}),
          flush=True)
    if result.get("notes"):
        print(json.dumps({"phase": "notes", **result["notes"]}), flush=True)
    if args.trace:
        print(json.dumps({"phase": "trace_window", **(
            {"start_share": tracer.start_share,
             "latest_start_s": tracer.latest_start_s} if tracer.by_share()
            else {"start_s": tracer.start_s}),
            "length_s": tracer.length_s, **tracer.placed}), flush=True)
    line["device"] = device
    if args.rehearse:
        line["rehearsal"] = True
    # each number compared beside its limit: last in the line, and the last
    # lines on standard error
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in result.get("compared", {}).items()}
    for k, c in line["compared"].items():
        print(f"compared {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

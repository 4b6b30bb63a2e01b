#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json``, its configuration from
``configs/<config>.json`` and its traffic from ``traffic/<traffic>.json``,
imports the driver the traffic's ``kind`` names (``drivers/<kind before the
first underscore>.py``) and, in a traced run, one reader per per-layer metric
(``metrics/<name>.json`` names it). The last line of standard output is the
result object; everything else goes on earlier lines. Fails, printing no
result, where JAX finds no TPU or fewer chips than the cell asks for.

``--rehearse`` (used by ``selftest.py`` only) runs the same control flow on
the CPU at a tiny width; its line says ``"rehearsal": true`` and its numbers
mean nothing.
"""

from __future__ import annotations

import time

T_IMPORT = time.time()

import argparse
import glob
import importlib
import json
import os
import shutil
import sys
import tempfile
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

TRACE_SECONDS = 3.0


def process_start() -> float:
    """Wall-clock instant this process was created (set-up starts there)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return min(T_IMPORT,
                   time.time() - (up - ticks / os.sysconf("SC_CLK_TCK")))
    except (OSError, ValueError, IndexError):
        return T_IMPORT


def log(msg: str) -> None:
    print(f"[bench {time.time() - T_IMPORT:8.2f}] {msg}", flush=True)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Context(types.SimpleNamespace):
    """What a driver gets: the cell's data, the clock marks of set-up, the
    window's edges and the profiler."""

    def mark(self, name: str) -> None:
        now = time.time()
        self.marks.append((name, now - self.last_mark))
        self.last_mark = now

    def open_window(self) -> float:
        self.compiles_at_open = len(self.compile_events)
        self.t_open = time.time()
        return self.t_open

    def close_window(self) -> None:
        self.compiles_in_window = \
            len(self.compile_events) - self.compiles_at_open

    def start_trace(self) -> None:
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="kft_bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("kft_bench_sync"):
            self.t_sync = time.time()
        self.t_trace = (time.time(), None)

    def stop_trace(self) -> None:
        import jax

        self.t_trace = (self.t_trace[0], time.time())
        jax.profiler.stop_trace()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", default="",
                    help="serve_open only: rates to try after one set-up, "
                         "comma separated; prints a table and no result")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the .xplane.pb here (for building readers)")
    a = ap.parse_args(argv)
    t_start = process_start()

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == a.workload),
                None)
    if cell is None:
        print(f"no workload {a.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    seconds = a.seconds if a.seconds is not None else bench["run_seconds"]

    from lib import model, traffic as traffic_lib

    if a.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    import jax

    from kubeflow_tpu.utils import compile_cache

    devices = jax.devices()
    if not a.rehearse and (devices[0].platform != "tpu"
                           or len(devices) < cell["chips"]):
        print(f"need {cell['chips']} TPU chip(s), JAX reports "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 3
    if not a.rehearse:
        log(f"compile cache: {compile_cache.ensure()}")
    compile_events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compile_events.append((time.time(), secs))
        if name == "/jax/core/compile/backend_compile_duration" else None)

    tr = traffic_lib.load(cell["traffic"])
    ctx = Context(
        cell=cell, seed=a.seed, seconds=float(seconds), trace=bool(a.trace),
        trace_seconds=min(TRACE_SECONDS, float(seconds) / 2),
        rehearse=a.rehearse, chips=cell["chips"], log=log,
        sweep=[float(x) for x in a.sweep.split(",") if x],
        config=model.load_config(cell["config"], tiny=a.rehearse),
        traffic=tr, marks=[("process_start", time.time() - t_start)],
        last_mark=time.time(), compile_events=compile_events,
        compiles_in_window=None, trace_dir=None, t_trace=None, t_sync=None)
    driver = importlib.import_module("drivers." + tr["kind"].split("_")[0])
    result = driver.run(ctx)
    if result is None:                         # a sweep: table only
        return 0

    setup_s = ctx.t_open - t_start
    log("set-up split: " + ", ".join(f"{k} {v:.2f}" for k, v in ctx.marks)
        + f"; setup_s {setup_s:.2f}; programs compiled or loaded in set-up "
        f"{ctx.compiles_at_open} taking "
        f"{sum(s for _, s in compile_events[:ctx.compiles_at_open]):.2f} s; "
        f"in window {ctx.compiles_in_window}")
    values = dict(result["values"], setup_s=setup_s)
    values["compile.in_window"] = float(ctx.compiles_in_window)
    correct = bool(result["correct"])

    stats = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(
                  (s.get("peak_bytes_in_use", 0) for s in stats), default=0)}
    line = {"correct": correct, "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": {}, "device": device}
    if a.rehearse:
        line["rehearsal"] = True

    if not ctx.trace:
        wanted = [m for m in bench["end_to_end"] if applies(m, cell["name"])]
        for m in wanted:
            v = values.get(m["name"])
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        from lib import xplane

        pb = glob.glob(os.path.join(ctx.trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
        if a.keep_trace and pb:
            os.makedirs(os.path.dirname(a.keep_trace) or ".", exist_ok=True)
            shutil.copy(pb[0], a.keep_trace)
        t0 = time.time()
        reduced = xplane.reduce(pb[0], ctx.t_sync, result["spans"],
                                cell["chips"]) if pb else None
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.time() - t0:.2f} s")
        run = types.SimpleNamespace(
            cell=cell, config=ctx.config, traffic=tr, values=values,
            spans=[s for s in result["spans"]
                   if s["t0"] >= ctx.t_open
                   and s["t1"] <= ctx.t_open + ctx.seconds],
            samples=result.get("samples", []), trace=reduced,
            t_trace=ctx.t_trace, device=device)
        for m in bench["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            with open(os.path.join(HERE, "metrics", m["name"] + ".json")) as f:
                spec = json.load(f)
            reader = importlib.import_module("readers." + spec["reader"])
            v = reader.read(run, **spec.get("args", {}))
            if v is not None:
                line["metrics"][m["name"]] = {"value": float(v),
                                              "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = reduced["breakdown"]
        if ctx.compiles_in_window:
            correct = line["correct"] = False
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

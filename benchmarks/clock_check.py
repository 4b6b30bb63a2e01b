#!/usr/bin/env python3
"""Check of the clock mapping that the trace reduction rests on.

    python3 benchmarks/clock_check.py --workload <serving cell> --seed <n>

``lib/xplane.py`` puts the program's host spans (``time.time()``) on the
profiler's clock through one annotation, ``kft_bench_sync``: a span's start
is ``sync_ns + (t0 - t_sync) * 1e9``. The engine enters a
``jax.profiler.TraceAnnotation`` of the same name where it opens each span
of its timeline (``engine.step`` and its phases), so the profiler's host
plane holds the same instants on its own clock. This runs the cell once
through ``run.py --trace 1 --keep-trace`` (its lines and its result line are
printed as ever), matches every such annotation to the span of that name
whose mapped start is nearest, and prints the disagreements: their median
and their largest, per name and over all. It also writes the window's spans
and the run's clock marks to ``<out>/<cell>.timeline.json``, for whoever
wants the engine thread's split by hand.

Not a metric and read by no metric: a tool for the builder of a reader.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile

NAMES = ("engine.step", "step.admit", "prefill.wait", "step.dispatch",
         "step.wait", "step.commit")


def disagreements(sync_ns, t_sync, marks, spans):
    """``{name: [ns, ...]}``: for each annotation start in ``marks[name]``
    (profiler clock, ns) its distance to the nearest mapped start of a span
    of that name (annotation minus span)."""
    out = {}
    for name, starts in marks.items():
        mapped = sorted(sync_ns + (s["t0"] - t_sync) * 1e9
                        for s in spans if s["name"] == name)
        if mapped:
            out[name] = [min((a - m for m in mapped), key=abs)
                         for a in starts]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default="chiprun_out/clock_check")
    ap.add_argument("--rehearse", action="store_true",
                    help="on the CPU at a tiny width, as selftest.py does")
    a = ap.parse_args(argv)

    import run
    from drivers import serve
    from lib import xplane

    kept = {}
    inner = serve.run

    def keeping(ctx):
        result = inner(ctx)
        kept.update(ctx=ctx, spans=result["spans"])
        return result

    serve.run = keeping
    with tempfile.TemporaryDirectory(prefix="kft_clock_check_") as tmp:
        pb = os.path.join(tmp, "trace.xplane.pb")
        args = ["--workload", a.workload, "--seed", str(a.seed),
                "--trace", "1", "--keep-trace", pb]
        if a.seconds is not None:
            args += ["--seconds", str(a.seconds)]
        if a.rehearse:
            args.append("--rehearse")
        rc = run.main(args)
        if rc or not os.path.exists(pb):
            print("clock check: no trace to read", file=sys.stderr)
            return rc or 1
        import jax

        sync_ns, marks = None, {}
        for plane in jax.profiler.ProfileData.from_file(pb).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == xplane.SYNC and sync_ns is None:
                        sync_ns = float(ev.start_ns)
                    elif ev.name in NAMES:
                        marks.setdefault(ev.name, []).append(
                            float(ev.start_ns))
    ctx = kept["ctx"]
    spans = [s for s in kept["spans"] if s["t0"] >= ctx.t_open
             and s["t1"] <= ctx.t_open + ctx.seconds]
    check = {"annotations": sum(len(v) for v in marks.values())}
    if sync_ns is None or not marks:
        print("clock check: the trace holds no kft_bench_sync or no "
              "annotation of the engine's timeline")
    else:
        diffs = disagreements(sync_ns, ctx.t_sync, marks, spans)
        diffs["all"] = [x for d in diffs.values() for x in d]
        for name, d in sorted(diffs.items()):
            check[name] = {"n": len(d),
                           "median_us": statistics.median(d) / 1e3,
                           "max_abs_us": max(map(abs, d)) / 1e3}
        print("clock check (annotation start minus mapped span start): "
              + json.dumps(check))
    os.makedirs(a.out, exist_ok=True)
    path = os.path.join(a.out, a.workload + ".timeline.json")
    with open(path, "w") as f:
        json.dump({"workload": a.workload, "seed": a.seed,
                   "t_open": ctx.t_open, "seconds": ctx.seconds,
                   "t_sync": ctx.t_sync, "t_trace": ctx.t_trace,
                   "clock_check": check, "spans": spans}, f)
    print(f"clock check: wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

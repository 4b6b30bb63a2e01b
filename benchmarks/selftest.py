#!/usr/bin/env python3
"""Rehearsal of the benchmark without a chip: ``python3 benchmarks/selftest.py``.

Not collected by ``tests/``. Checks, in this order:

1. ``lib/window.py`` gives the exact rate on a synthetic list of commits
   (rule 2) and the percentile numpy would give;
2. every run replays the same work: each pass of a traffic file's frozen list
   has the same prompt tokens, output tokens and time to fall due in, the
   window holds whole passes of an open loop's list, those passes leave ten
   requests or more beyond the 90th percentile, ``rate_why`` opens with
   the ``rate_rps`` the file holds, and the seed changes the token ids only
   (rule 1);
3. the trace reduction gives hand-computed busy time, program and kernel time,
   exposed collective time and idle gaps on a synthetic event list, and the
   recorded values on the small recorded trace in ``fixtures/``;
4. every file that ``BENCHMARK.json`` names exists, and every cell reports
   ``setup_s``, another end-to-end metric and a per-layer metric;
5. every cell runs end to end on the CPU at a tiny width (``run.py
   --rehearse``; four virtual devices for a four-chip cell), untraced and
   traced, and its last line has exactly the contract's keys.

``--quick`` skips 5, which takes a few minutes.
"""

from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

from lib import traffic, window, xplane  # noqa: E402


def check_window():
    # 4 tokens at each commit, 0.5 s apart; the window opens between commits
    commits = [(10.0 + 0.5 * k, 4 * k) for k in range(100)]
    w = window.commit_window(commits, t_open=12.2, seconds=10.0)
    assert w == (12.5, 22.5, 80), w
    assert window.commit_rate(commits, 12.2, 10.0) == 8.0
    # one long commit just past the edge changes nothing
    late = commits[:26] + [(22.6, 1000)]
    assert window.commit_rate(late, 12.2, 10.0) == 8.0
    assert window.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == 9.1
    assert window.percentile([], 90) is None


def check_traffic(bench):
    # every file there, a cell's or not yet
    for name in sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "traffic"))
                       if f.endswith(".json")):
        tr = traffic.load(name)
        if "pairs" not in tr:
            continue
        n = len(tr["pairs"])
        gen = traffic.requests(tr)
        rows = [next(gen) for _ in range(3 * n + 1)]
        # every pass is the same work, due in the same time
        passes = [rows[k * n:(k + 1) * n] for k in range(3)]
        assert len({(sum(r[2] for r in p), sum(r[3] for r in p))
                    for p in passes}) == 1, name
        if "gaps_unit" in tr:
            period = traffic.period_s(tr)
            assert abs(rows[3 * n][1] - rows[0][1] - 3 * period) < 1e-6
            # the window holds whole passes: the same requests in every run
            whole = round(bench["run_seconds"] / period)
            assert abs(bench["run_seconds"] / period - whole) < 1e-3, period
            # a p90 with neighbours: ten judged requests or more beyond it
            judged = whole * n
            assert judged - math.ceil(0.9 * judged) >= 10, (name, judged)
            # a rate edited without its sentence is a rate nobody re-derived:
            # the sentence opens with the rate, "6.0 rps = 0.625 of ..."
            assert tr["rate_why"].startswith(f"{tr['rate_rps']} rps = "), name
        # the seed makes the ids and nothing else; the same seed, the same ids
        assert traffic.token_ids(7, 3, 16, 1000) == \
            traffic.token_ids(7, 3, 16, 1000)
        assert traffic.token_ids(7, 3, 16, 1000) != \
            traffic.token_ids(2**31 + 7, 3, 16, 1000)


def check_xplane():
    ms = 1e6
    mods = [(0.0, 10 * ms, "jit_step(123)"), (20 * ms, 10 * ms, "jit_step(123)")]
    call = "%call.7 = bf16[8,128]{1,0:T(8,128)} custom-call(bf16[8]{0} %p)"
    ops = [(0.0, 10 * ms, "%while.3 = (s32[]) while((s32[]) %t)"),  # covers 3
           (0.0, 4 * ms, "fusion.1"), (4 * ms, 2 * ms, call),
           (6 * ms, 1 * ms, "all-gather-done.2"),
           (20 * ms, 4 * ms, "fusion.1"), (24 * ms, 2 * ms, call),
           (26 * ms, 4 * ms, "fusion.9")]
    # started under the kernel, done 3 ms after it: 1 ms hidden, 3 exposed
    asyncs = [(5 * ms, 4 * ms, "%all-gather-start.2 = bf16[8] all-gather-start(")]
    devices = {"/device:TPU:0": {xplane.OPS_LINE: sorted(ops),
                                 xplane.ASYNC_LINE: asyncs,
                                 xplane.MODULES_LINE: mods}}
    spans = [{"name": "decode.step", "t0": 100.010, "t1": 100.021}]
    r = xplane.reduce_events(0.0, devices, 100.0, spans, 1, t_stop=100.030)
    near = lambda a, b: abs(a - b) < 1e-9
    assert near(r["window_s"], 0.030), r["window_s"]
    assert near(r["busy_s"], 0.020), r["busy_s"]       # the loop covers 10 ms
    assert near(r["collective_exposed_s"], 0.0)        # ... and the gather
    assert near(r["ops"][0]["seconds"], 0.008), r["ops"][0]
    loop = next(o for o in r["ops"] if o["name"] == "while.3 while s32[]")
    assert near(loop["seconds"], 0.003), loop          # self time
    assert near(r["programs"]["jit_step"]["seconds"], 0.020)
    assert r["programs"]["jit_step"]["count"] == 2
    (k,) = r["kernels"]
    assert k["name"] == "call.7 custom-call bf16[8,128]", k
    assert near(k["seconds"], 0.004) and k["count"] == 2
    assert r["breakdown"]["device_ops"][0] == ["jit_step/fusion.1", 0.008]
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert near(gaps["decode.step"], 0.010), gaps

    fixture = os.path.join(HERE, "fixtures", "decode.xplane.pb")
    with open(os.path.join(HERE, "fixtures", "decode.expected.json")) as f:
        want = json.load(f)
    sync_ns, devs = xplane.load(fixture)
    r = xplane.reduce_events(sync_ns, devs, 0.0, [], 1)
    got = {"busy_s": r["busy_s"], "window_s": r["window_s"],
           "kernel_s": sum(k["seconds"] for k in r["kernels"]),
           "kernel_calls": sum(k["count"] for k in r["kernels"]),
           "decode_program_s": sum(v["seconds"] for p, v in
                                   r["programs"].items() if "decode" in p)}
    for key, v in want.items():
        assert abs(got[key] - v) <= 1e-6 * max(1.0, abs(v)), (key, got[key], v)


def applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def check_files(bench):
    e2e = bench["end_to_end"]
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names), names
    assert all(len({x["name"] for x in bench[k]}) == len(bench[k])
               for k in ("configs", "workloads", "end_to_end", "per_layer"))
    for m in e2e + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher"), m
    for m in e2e:
        assert 0.01 <= m["bound"] <= 0.1, m
    for x in bench["configs"] + bench["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"], x
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4), four
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"])), c["file"]
    for w in bench["workloads"]:
        traffic.load(w["traffic"])
        mine = [m["name"] for m in e2e if applies(m, w["name"])]
        assert "setup_s" in mine and len(mine) >= 2, (w["name"], mine)
        layer = [m for m in bench["per_layer"] if applies(m, w["name"])]
        assert layer, w["name"]
        for m in layer:
            assert m["moves"] in mine, (w["name"], m["name"], m["moves"])
    for m in bench["per_layer"]:
        with open(os.path.join(HERE, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            HERE, "readers", spec["reader"] + ".py")), spec


def rehearse(bench):
    keys = {"correct", "attempted", "failed", "metrics", "device",
            "rehearsal"}
    for w in bench["workloads"]:
        for trace in (0, 1):
            cmd = [sys.executable, os.path.join(HERE, "run.py"), "--rehearse",
                   "--workload", w["name"], "--seed", str(2**31 + 7 + trace),
                   "--seconds", "4", "--trace", str(trace)]
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=1500)
            assert out.returncode == 0, out.stderr[-2000:]
            line = json.loads(out.stdout.strip().splitlines()[-1])
            assert set(line) == keys, (w["name"], sorted(line))
            assert line["correct"] is True and line["failed"] == 0, line
            assert line["device"]["count"] == w["chips"], line["device"]
            want = bench["per_layer"] if trace else bench["end_to_end"]
            names = {m["name"] for m in want if applies(m, w["name"])}
            assert set(line["metrics"]) <= names, line["metrics"]
            if not trace:      # device metrics are absent on the CPU
                assert set(line["metrics"]) == names, line["metrics"]
            print(f"rehearsed {w['name']} --trace {trace}: "
                  f"{sorted(line['metrics'])}", flush=True)


def main() -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    check_window()
    check_traffic(bench)
    check_xplane()
    check_files(bench)
    print("window, traffic, trace reduction and files: ok", flush=True)
    if "--quick" not in sys.argv:
        rehearse(bench)
    print("selftest ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())

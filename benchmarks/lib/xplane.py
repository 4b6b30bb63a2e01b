"""Reduction of a profiler trace (``.xplane.pb``) to what the readers need.

Reads the trace with ``jax.profiler.ProfileData`` only. A TPU's plane is named
``/device:TPU:<n>``; its line ``XLA Modules`` has one event per execution of a
jitted program (named ``jit_<function>(<fingerprint>)``) and its line
``XLA Ops`` one event per operation, named by its HLO text (``%fusion.1 =
bf16[...] fusion(...)``); loops (``while``) are events that cover their body's
events, so an operation's time here is its *self* time, without what it covers.
``Async XLA Ops`` has the whole span of asynchronous operations (collectives,
copies) from start to done. An operation belongs to the program whose event
covers its start. Host spans (the engine's ``decode.step`` and
``prefill.batch``, on ``time.time()``) are put on the trace's clock through
the ``kft_bench_sync`` annotation the harness writes right after the trace
starts.

``reduce`` returns, averaged over the device planes found:

    window_s   length of the traced window (sync annotation to last event)
    busy_s     union of the intervals in which an operation ran
    programs   {program: {"count", "seconds"}} from the modules line
    ops        [{"program", "name", "seconds", "count"}], largest first
    kernels    subset of ops whose name marks a Pallas kernel (custom-call)
    collective_exposed_s  collective time (either line) with no other
               operation running on that device
    breakdown  {"device_ops": [[name, s], ...], "idle_gaps": [[what, s], ...]}
"""

from __future__ import annotations

import bisect
import re

SYNC = "kft_bench_sync"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HLO = re.compile(r"^%(\S+) = (\(?[a-z0-9]+\[[^\]]*\])?.*? ([a-z][\w\-]*)\(")
SHORT_GAP_NS = 20_000
KERNEL_MARK = re.compile(r"custom-call|custom_call|pallas|mosaic", re.I)
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute",
    re.I)


def _program_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name.strip())


def short_name(hlo_text: str) -> str:
    """``%fusion.1 = bf16[8,128]{...} fusion(...)`` -> ``fusion.1 fusion
    bf16[8,128]``; anything else is cut to 80 characters."""
    m = HLO.match(hlo_text)
    if not m:
        return hlo_text[:80]
    return " ".join(x for x in (m.group(1), m.group(3),
                                (m.group(2) or "").lstrip("(")) if x)


def _self_times(events):
    """``[(start, dur, name)]`` sorted by start -> self durations: an event's
    own duration minus the events it covers (one level down)."""
    out = [dur for _, dur, _ in events]
    stack = []                                   # indices of open events
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    for i in order:
        s, dur, _ = events[i]
        while stack and events[stack[-1]][0] + events[stack[-1]][1] <= s:
            stack.pop()
        if stack and s + dur <= events[stack[-1]][0] + events[stack[-1]][1]:
            out[stack[-1]] -= dur
        stack.append(i)
    return out


def _union(intervals):
    """Merged, sorted ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def load(path: str):
    """``(sync_ns, {device plane name: {line name: [(start_ns, dur_ns, name,
    stats)]}})`` — everything the reduction reads, in plain tuples."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    sync_ns, devices = None, {}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        if not is_device and not plane.name.startswith("/host:"):
            continue
        lines = {}
        for line in plane.lines:
            if is_device and line.name not in (OPS_LINE, ASYNC_LINE,
                                               MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                if is_device:
                    events.append((float(ev.start_ns), float(ev.duration_ns),
                                   ev.name))
                elif ev.name == SYNC and sync_ns is None:
                    sync_ns = float(ev.start_ns)
            if events:
                lines[line.name] = sorted(events)
        if is_device and lines:
            devices[plane.name] = lines
    return sync_ns, devices


def reduce(path: str, t_sync: float, spans, chips: int, t_stop=None):
    sync_ns, devices = load(path)
    return reduce_events(sync_ns, devices, t_sync, spans, chips, t_stop)


def reduce_events(sync_ns, devices, t_sync, spans, chips, t_stop=None):
    if not devices:
        return None
    names = sorted(devices)[:chips]
    n = len(names)
    op_events = [ev for d in names for ev in devices[d].get(OPS_LINE, [])]
    start_ns = sync_ns if sync_ns is not None else \
        min((ev[0] for ev in op_events), default=0.0)
    end_ns = max((ev[0] + ev[1] for ev in op_events), default=start_ns)
    if t_stop is not None and sync_ns is not None:
        end_ns = max(end_ns, sync_ns + (t_stop - t_sync) * 1e9)

    programs, ops, busy_ns = {}, {}, 0.0
    exposed_ns = 0.0
    gaps0 = []
    for d in names:
        lines = devices[d]
        mods = lines.get(MODULES_LINE, [])
        mod_starts = [m[0] for m in mods]
        for s, dur, name in mods:
            p = programs.setdefault(_program_name(name),
                                    {"count": 0, "seconds": 0.0})
            p["count"] += 1.0 / n
            p["seconds"] += dur / 1e9 / n
        compute, collective = [], []
        events = lines.get(OPS_LINE, [])
        for (s, dur, name), own in zip(events, _self_times(events)):
            k = bisect.bisect_right(mod_starts, s) - 1
            prog = "?"
            if k >= 0 and s < mods[k][0] + mods[k][1]:
                prog = _program_name(mods[k][2])
            o = ops.setdefault((prog, short_name(name)),
                               {"count": 0.0, "seconds": 0.0})
            o["count"] += 1.0 / n
            o["seconds"] += max(own, 0.0) / 1e9 / n
            s, e = max(s, start_ns), min(s + dur, end_ns)
            if e > s:
                (collective if COLLECTIVE.search(name)
                 else compute).append((s, e))
        for s, dur, name in lines.get(ASYNC_LINE, []):
            s, e = max(s, start_ns), min(s + dur, end_ns)
            if e > s and COLLECTIVE.search(name):
                collective.append((s, e))
        compute, collective = _union(compute), _union(collective)
        busy = _union(compute + collective)
        busy_ns += _length(busy) / n
        exposed_ns += _length(_subtract(collective, compute)) / n
        if d == names[0]:
            gaps0 = _subtract([(start_ns, end_ns)], busy)

    op_list = sorted(({"program": p, "name": nm, **v}
                      for (p, nm), v in ops.items()),
                     key=lambda o: -o["seconds"])
    # idle gaps of the first device, named by the host spans that cover them
    host = []
    if sync_ns is not None:
        for sp in spans:
            if sp.get("t1") is None:
                continue
            a = sync_ns + (sp["t0"] - t_sync) * 1e9
            b = sync_ns + (sp["t1"] - t_sync) * 1e9
            if b >= start_ns and a <= end_ns:
                host.append((a, b, sp["name"]))
    by_what = {}
    for s, e in gaps0:
        if e - s < SHORT_GAP_NS:
            what = "between_ops_under_%dus" % (SHORT_GAP_NS // 1000)
        else:
            mid = (s + e) / 2
            what = "_".join(sorted(
                {nm for a, b, nm in host if a <= mid <= b})) or "no_span"
        by_what[what] = by_what.get(what, 0.0) + (e - s) / 1e9
    breakdown = {
        "device_ops": [[f"{o['program']}/{o['name']}"[:96], o["seconds"]]
                       for o in op_list[:10]],
        "idle_gaps": sorted(([k, v] for k, v in by_what.items()),
                            key=lambda kv: -kv[1])[:10],
    }
    return {
        "window_s": (end_ns - start_ns) / 1e9,
        "busy_s": busy_ns / 1e9,
        "collective_exposed_s": exposed_ns / 1e9,
        "programs": programs,
        "ops": op_list,
        "kernels": [o for o in op_list if KERNEL_MARK.search(o["name"])],
        "breakdown": breakdown,
    }

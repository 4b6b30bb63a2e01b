"""The engine thread's timeline (ISSUE 26): every ``LLMEngine.step()`` that
does anything records one ``engine.step`` span tiled by phase spans
(``step.admit`` / ``prefill.wait`` / ``step.dispatch`` / ``step.wait`` /
``step.commit``), the spans and attrs that were there before are unchanged,
and the benchmark's readers of the timeline (``benchmarks/readers/quiet.py``,
``engine_host.py``) give hand-computed values and cut at the profiler's
start."""

import os
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.models import llama
from kubeflow_tpu.obs import export, trace
from kubeflow_tpu.obs.histogram import Histogram
from kubeflow_tpu.serving.llm import LLMEngine, SamplingParams
from kubeflow_tpu.serving.scheduler import SchedulerConfig

BENCHMARKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")

PHASES = {"step.admit", "prefill.wait", "step.dispatch", "step.wait",
          "step.commit"}
# the attrs each span that predates the timeline carries when it closes
# (README "Observability contract"; the benchmark's accepted metrics and
# lib/xplane.py read them)
OLD_ATTRS = {
    "request.queue": {"request_id", "prompt_tokens", "slot",
                      "shared_blocks"},
    "prefill.batch": {"bucket", "batch"},
    "prefill.chunk": {"slot", "offset", "width", "prompt_tokens", "final"},
    "decode.step": {"chunk_len", "batch", "tokens_committed",
                    "device_steps"},
    "decode.verify": {"width", "drafted", "batch", "tokens_committed"},
}


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    return params, cfg


class _AlwaysDrafts:
    """Drafts that the target never confirms (token 0): every round still
    dispatches a verify, which is what the timeline has to show."""

    k = 3

    def draft(self, context):
        return [0, 0, 0]


def _drain(eng):
    while eng.has_work():
        eng.step()


def _pipelined(params, cfg, col):
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(16,), obs=col)
    reqs = [eng.add_request(list(range(1, 9)), SamplingParams(max_tokens=12))
            for _ in range(3)]                 # the third waits for a slot
    _drain(eng)
    return eng, reqs


def _synchronous(params, cfg, col):
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(16,), decode_pipeline=False, obs=col)
    reqs = [eng.add_request([3, 4, 5], SamplingParams(max_tokens=10))
            for _ in range(2)]
    _drain(eng)
    return eng, reqs


def _chunked(params, cfg, col):
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=128,
                    prefill_buckets=(16,), obs=col)
    live = eng.add_request([5, 6, 7], SamplingParams(max_tokens=24))
    eng.step()
    long_prompt = [(7 * i) % 250 + 1 for i in range(50)]   # 4 chunks of 16
    long = eng.add_request(long_prompt, SamplingParams(max_tokens=6))
    _drain(eng)
    assert eng.sched.prefill_chunks >= 4
    return eng, [live, long]


def _speculative(params, cfg, col):
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(8,), obs=col,
                    scheduler=SchedulerConfig(spec_decode=True, spec_k=3))
    eng.spec = _AlwaysDrafts()
    reqs = [eng.add_request(p, SamplingParams(max_tokens=8))
            for p in ([5, 6, 7], [9, 10])]
    _drain(eng)
    assert eng.sched.spec_dispatches > 0
    return eng, reqs


def _abort_mid_flight(params, cfg, col):
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=64,
                    prefill_buckets=(16,), obs=col)
    keep = eng.add_request([1, 2, 3], SamplingParams(max_tokens=20))
    drop = eng.add_request([4, 5, 6], SamplingParams(max_tokens=20))
    eng.step()
    eng.step()                     # a chunk holding both is in flight
    eng.abort([drop])
    _drain(eng)
    assert drop.aborted and len(drop.generated) < 20
    return eng, [keep]


SCENARIOS = {"pipelined": _pipelined, "synchronous": _synchronous,
             "chunked_prefill": _chunked, "speculative": _speculative,
             "abort_mid_flight": _abort_mid_flight}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_phases_tile_every_engine_step(tiny, scenario):
    params, cfg = tiny
    col = trace.SpanCollector(capacity=4096, proc=scenario)
    eng, reqs = SCENARIOS[scenario](params, cfg, col)
    assert all(r.done for r in reqs)
    assert col.open_count == 0                   # drained: nothing dangles
    assert eng._step_span is None and eng._cur_phase is None
    spans = col.snapshot()
    assert not export.validate_trace(spans)
    steps = [s for s in spans if s["name"] == "engine.step"]
    assert steps
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s["parent_id"], []).append(s)
    for st in steps:
        kids = sorted(by_parent.get(st["span_id"], []),
                      key=lambda s: s["t0"])
        assert kids, st
        assert {k["name"] for k in kids} <= PHASES
        assert all(k["trace_id"] == st["trace_id"] for k in kids)
        assert st["t0"] <= kids[0]["t0"] and kids[-1]["t1"] <= st["t1"]
        for a, b in zip(kids, kids[1:]):
            assert a["t1"] <= b["t0"], (a, b)    # no instant in two phases
        assert {"waiting", "active", "free_slots", "admitted",
                "stalled"} <= set(st["attrs"])

        def inside(t):
            return st["t0"] <= t <= st["t1"]

        names = [k["name"] for k in kids]
        launched = [s for s in spans
                    if s["name"] in ("decode.step", "decode.verify")
                    and inside(s["t0"])]
        if launched:
            assert "step.dispatch" in names, (scenario, names)
        if any(s["name"] in ("decode.step", "decode.verify")
               and inside(s["t1"]) for s in spans):        # read back
            waits = [k for k in kids if k["name"] == "step.wait"]
            assert waits and all(k["attrs"]["device_steps"] >= 1
                                 for k in waits)
            assert "step.commit" in names
    # the phases are the only children: every phase span names a step
    ids = {st["span_id"] for st in steps}
    assert all(s["parent_id"] in ids for s in spans if s["name"] in PHASES)
    # the counts at the boundaries add up to what the requests got: one
    # token at admission (inside step.admit), the rest in step.commit
    committed = sum(s["attrs"]["tokens_committed"] for s in spans
                    if s["name"] == "step.commit")
    admitted = sum(st["attrs"]["admitted"] for st in steps)
    assert committed + admitted == eng.generated_tokens
    device_steps = sum(s["attrs"]["device_steps"] for s in spans
                       if s["name"] == "step.wait")
    assert device_steps == eng.steps
    # what was there before is there still, name for name, attr for attr
    seen = {s["name"] for s in spans}
    assert {"request.queue", "decode.step" if scenario != "speculative"
            else "decode.verify"} <= seen
    for s in spans:
        want = OLD_ATTRS.get(s["name"])
        if want is not None and not s["attrs"].get("aborted"):
            assert want <= set(s["attrs"]), s


def test_idle_step_records_nothing_and_stall_is_counted(tiny):
    params, cfg = tiny
    col = trace.SpanCollector(capacity=1024)
    # 2 usable blocks of 16 tokens: the second request cannot reserve
    eng = LLMEngine(params, cfg, max_batch=2, max_seq=32,
                    prefill_buckets=(16,), kv_block_size=16,
                    kv_num_blocks=3, obs=col)
    # the engine's construction left its one span; an idle step none
    assert eng.step() == []
    assert [s["name"] for s in col.snapshot()] == ["engine.build"]
    a = eng.add_request([1, 2, 3], SamplingParams(max_tokens=20))
    b = eng.add_request([4, 5, 6], SamplingParams(max_tokens=20))
    eng.step()
    first = [s for s in col.snapshot() if s["name"] == "engine.step"][0]
    assert first["attrs"]["waiting"] == 2 and first["attrs"]["active"] == 0
    assert first["attrs"]["free_slots"] == 2
    assert first["attrs"]["admitted"] == 1 and first["attrs"]["stalled"] == 1
    _drain(eng)
    assert a.done and b.done and col.open_count == 0
    n = len(col.snapshot())
    eng.step()
    assert len(col.snapshot()) == n


def test_failing_step_closes_its_spans(tiny):
    """An exception out of a phase leaves no span open and no phase behind
    (the next step() starts a clean timeline)."""
    params, cfg = tiny
    col = trace.SpanCollector(capacity=256)
    eng = LLMEngine(params, cfg, max_batch=1, max_seq=64,
                    prefill_buckets=(16,), obs=col)
    req = eng.add_request([1, 2, 3], SamplingParams(max_tokens=4))
    good = eng._sample_rows

    def boom(*a, **kw):
        with eng._phase("prefill.wait"):
            raise RuntimeError("device lost")

    eng._sample_rows = boom
    with pytest.raises(RuntimeError):
        eng.step()
    assert eng._step_span is None and eng._cur_phase is None
    open_now = col.open_count          # the admission's own prefill.batch
    assert {s["name"] for s in col.snapshot()} >= {
        "engine.step", "step.admit", "prefill.wait"}
    eng._sample_rows = good
    eng.abort([req])
    _drain(eng)
    assert col.open_count == open_now


def test_histogram_observe_n_equals_n_single_observes():
    many, single = Histogram(), Histogram()
    for value, n in ((0.0042, 7), (0.3, 1), (100.0, 3), (0.0042, 2)):
        many.observe(value, n)
        for _ in range(n):
            single.observe(value)
    a, b = many.snapshot(), single.snapshot()
    assert a.pop("sum") == pytest.approx(b.pop("sum"), rel=1e-12)
    assert a == b and many.count == 13


def test_ids_are_w3c_sized_and_distinct():
    tids = {trace.new_trace_id() for _ in range(2000)}
    sids = {trace.new_span_id() for _ in range(2000)}
    assert len(tids) == 2000 and len(sids) == 2000
    assert all(len(t) == 32 and int(t, 16) for t in tids)
    assert all(len(s) == 16 and int(s, 16) for s in sids)
    t, s = next(iter(tids)), next(iter(sids))
    assert trace.parse_traceparent(trace.format_traceparent(t, s)) == (t, s)


# ------------------------------------------------ the benchmark's readers --


@pytest.fixture()
def readers(monkeypatch):
    monkeypatch.syspath_prepend(BENCHMARKS)
    for name in [m for m in sys.modules
                 if m == "readers" or m.startswith("readers.")]:
        monkeypatch.delitem(sys.modules, name)
    from readers import engine_host, quiet

    yield types.SimpleNamespace(engine_host=engine_host, quiet=quiet)
    for name in [m for m in sys.modules
                 if m in ("readers", "lib") or m.startswith(("readers.",
                                                             "lib."))]:
        sys.modules.pop(name, None)


def _span(name, t0, t1, span_id=None, parent_id=None, **attrs):
    return {"name": name, "t0": t0, "t1": t1, "span_id": span_id or name,
            "parent_id": parent_id, "attrs": attrs}


def _timeline():
    """Two steps before the profiler starts at t=20, one after it."""
    return [
        # step A, 10.0-10.4: 0.1 admit of which 0.04 waits; 0.25 step.wait
        _span("engine.step", 10.0, 10.4, "A"),
        _span("step.admit", 10.0, 10.03, "a1", "A"),
        _span("prefill.wait", 10.03, 10.07, "a2", "A"),
        _span("prefill.batch", 10.01, 10.08, "pb1", batch=1),
        _span("step.admit", 10.07, 10.1, "a3", "A"),
        _span("step.dispatch", 10.1, 10.12, "a4", "A"),
        _span("step.wait", 10.12, 10.37, "a5", "A", device_steps=8),
        _span("step.commit", 10.37, 10.4, "a6", "A", tokens_committed=16),
        # step B, 10.5-11.0: 0.45 step.wait of 4 device steps
        _span("engine.step", 10.5, 11.0, "B"),
        _span("step.admit", 10.5, 10.51, "b1", "B"),
        _span("step.wait", 10.52, 10.97, "b2", "B", device_steps=4),
        _span("step.commit", 10.97, 11.0, "b3", "B", tokens_committed=8),
        _span("prefill.batch", 10.6, 10.9, "pb2", batch=2),
        # the profiler starts at 20.0 and stalls the thread; this step and
        # this admission are long because of it
        _span("engine.step", 20.5, 29.5, "C"),
        _span("step.wait", 20.5, 21.0, "c1", "C", device_steps=8),
        _span("prefill.batch", 21.0, 29.0, "pb3", batch=1),
    ]


def _run(spans, t_trace=(20.0, 23.0)):
    return types.SimpleNamespace(spans=spans, t_trace=t_trace)


def test_engine_host_reader_hand_computed(readers):
    run = _run(_timeline())
    # host = (0.4 + 0.5) - (0.04 + 0.25 + 0.45) = 0.16 s over 12 decode steps
    assert readers.engine_host.read(run, per="decode_step") == \
        pytest.approx(1000 * 0.16 / 12)
    # ... and over the 1.0 s from the first step's start to the last's end
    assert readers.engine_host.read(run, per="wall") == pytest.approx(16.0)
    with pytest.raises(ValueError):
        readers.engine_host.read(run, per="fortnight")


def test_quiet_reader_p90_and_cut_at_profiler_start(readers):
    run = _run(_timeline())
    # prefill.batch durations before the cut: 0.07 and 0.3
    got = readers.quiet.read(run, span="prefill.batch", stat="p90")
    assert got == pytest.approx(0.07 + 0.9 * (0.3 - 0.07))
    assert readers.quiet.read(run, span="prefill.batch", attr="batch",
                              stat="mean") == pytest.approx(1.5)
    # the long spans after the profiler's start change nothing ...
    before = [s for s in _timeline() if s["t1"] <= 11.0]
    for per in ("decode_step", "wall"):
        assert readers.engine_host.read(_run(before), per=per) == \
            readers.engine_host.read(run, per=per)
    assert readers.quiet.read(_run(before), span="prefill.batch",
                              stat="p90") == got
    # ... and a span that was open across the cut is not read either
    straddling = _timeline() + [_span("prefill.batch", 10.9, 25.0, "pb4")]
    assert readers.quiet.read(_run(straddling), span="prefill.batch",
                              stat="p90") == got


@pytest.mark.parametrize("spans, t_trace", [
    ([], (20.0, 23.0)),                                    # no spans
    ([_span("decode.step", 10.0, 10.4, batch=2, chunk_len=8),
      _span("prefill.batch", 10.0, 10.1)], (20.0, 23.0)),  # the parent's
    (_timeline(), None),                                   # never traced
    ([s for s in _timeline() if s["t0"] >= 20.0], (20.0, 23.0)),
], ids=["no_spans", "no_engine_step", "no_trace", "nothing_before_start"])
def test_timeline_readers_return_none_without_a_quiet_part(readers, spans,
                                                           t_trace):
    run = _run(spans, t_trace)
    assert readers.engine_host.read(run, per="decode_step") is None
    assert readers.engine_host.read(run, per="wall") is None
    assert readers.quiet.read(run, span="prefill.batch", stat="p90") is None
    assert readers.quiet.spans(run) == []


def test_engine_spans_feed_the_readers(tiny, readers):
    """The engine's real spans through the real readers: host work per
    decode step is positive and smaller than the step's whole wall time."""
    params, cfg = tiny
    col = trace.SpanCollector(capacity=4096)
    eng, _ = _pipelined(params, cfg, col)
    spans = col.snapshot()
    end = max(s["t1"] for s in spans)
    run = _run(spans, (end + 1.0, end + 2.0))
    per_step = readers.engine_host.read(run, per="decode_step")
    busy = readers.engine_host.read(run, per="wall")
    wall = readers.quiet.read(run, span="prefill.batch", stat="p90")
    assert per_step > 0 and 0 < busy <= 100 and wall > 0
    whole = sum(s["t1"] - s["t0"] for s in spans
                if s["name"] == "engine.step")
    assert per_step * eng.steps / 1000 < whole

"""Telemetry plane: metrics registry, span tracer, exporters, gating.

Covers the unified :class:`MetricsRegistry` (labeled families, weakref
providers, typed events, Prometheus dump), the request-scoped
:class:`Tracer` (flow root/step/end semantics, bounded buffer, Chrome
export schema), an end-to-end traced sim run validated by
:func:`validate_chrome_trace`, and the ``REPRO_TELEMETRY``-disabled
path: the shared no-op tracer records nothing and tracing on/off does
not change the executable plane's output bits.
"""

import gc
import json
import math

import numpy as np
import pytest

from repro.core import LocalBackend, ServingSystem
from repro.core.telemetry import (
    FoldCacheEviction,
    MetricsRegistry,
    configure,
    default_registry,
    telemetry_enabled,
    validate_chrome_trace,
)
from repro.core.tracing import COORDINATOR_PID, NULL_TRACER, Tracer, make_tracer


@pytest.fixture
def tele_on():
    prev = configure(True)
    yield
    configure(prev)


@pytest.fixture
def tele_off():
    prev = configure(False)
    yield
    configure(prev)


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

def test_registry_families_and_prometheus_dump():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests seen", labelnames=("wf",))
    c.labels("toy").inc()
    c.labels(wf="toy").inc(2)
    reg.gauge("fleet_size").set(4)
    h = reg.histogram("lat_seconds", bounds=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    txt = reg.to_prometheus()
    assert 'requests_total{wf="toy"} 3' in txt
    assert "# TYPE requests_total counter" in txt
    assert "fleet_size 4" in txt
    assert 'lat_seconds_bucket{le="0.1"} 1' in txt
    assert 'lat_seconds_bucket{le="1.0"} 2' in txt
    assert 'lat_seconds_bucket{le="+Inf"} 3' in txt
    assert "lat_seconds_count 3" in txt
    assert "lat_seconds_sum 5.55" in txt


def test_registry_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    # same kind re-registers onto the same family
    assert reg.counter("x_total") is reg.counter("x_total")


def test_registry_label_arity_checked():
    reg = MetricsRegistry()
    fam = reg.counter("y_total", labelnames=("a", "b"))
    with pytest.raises(ValueError):
        fam.labels("only-one")


def test_registry_providers_sum_and_weakref():
    class Obj:
        def __init__(self, n):
            self.n_things = n
            self.note = "not-numeric"

    reg = MetricsRegistry()
    a, b = Obj(2), Obj(3)
    # missing + non-numeric attrs are skipped, numeric ones summed
    reg.register_object("exec", a, ("n_things", "note", "missing"))
    reg.register_object("exec", b, ("n_things",))

    def sample():
        return {(n, tuple(sorted(l.items()))): v
                for n, l, _, v in reg.collect()}

    assert sample()[("exec_n_things", ())] == 5.0
    del a
    gc.collect()
    assert sample()[("exec_n_things", ())] == 3.0   # dead provider dropped


def test_registry_provider_labels_keep_series_apart():
    class Obj:
        n_failures = 1

    reg = MetricsRegistry()
    a, b = Obj(), Obj()          # keep refs alive: providers are weakrefs
    reg.register_object("executor", a, ("n_failures",),
                        labels={"executor": "0"})
    reg.register_object("executor", b, ("n_failures",),
                        labels={"executor": "1"})
    txt = reg.to_prometheus()
    assert 'executor_n_failures{executor="0"} 1' in txt
    assert 'executor_n_failures{executor="1"} 1' in txt


def test_registry_typed_events_ring_and_counter():
    reg = MetricsRegistry()
    ev = FoldCacheEviction(model_id="base", patch_ids=("p1",),
                           resident_bytes=1024.0)
    reg.emit(ev)
    assert reg.events_of(FoldCacheEviction) == [ev]
    assert 'telemetry_events_total{type="FoldCacheEviction"} 1' \
        in reg.to_prometheus()


def test_fold_cache_eviction_emits_typed_event_and_compat_marker():
    """An eviction from the fold cache is signalled by one typed event
    and leaves no entry in ``forward_log``, which counts forwards only."""

    class _StubModel:
        model_id = "base"

        def load(self, device=None):
            return {"w": np.zeros(256, np.float32)}     # 1 KiB

        def fold_patches(self, comps, patches, patch_comps):
            return {"w": comps["w"] + len(patches)}

    class _StubPatch:
        def __init__(self, mid):
            self.model_id = mid

        def load(self, device=None):
            return {"a": np.zeros(256, np.float32)}

    reg = default_registry()
    before = len(reg.events_of(FoldCacheEviction))
    be = LocalBackend(folded_budget_bytes=2.5 * 1024)
    base = _StubModel()
    folds = [[_StubPatch(f"p{i}")] for i in range(3)]
    be.components_for(base, folds[0])
    be.components_for(base, folds[1])
    be.components_for(base, folds[0])           # refresh placement 0
    be.components_for(base, folds[2])           # evicts placement 1 (LRU)
    evs = reg.events_of(FoldCacheEviction)[before:]
    assert len(evs) == 1
    assert evs[0].model_id == "base"
    assert evs[0].patch_ids == ("p1",)
    assert evs[0].resident_bytes > 0
    assert len(be.forward_log) == 0


# --------------------------------------------------------------------------
# tracer
# --------------------------------------------------------------------------

def test_tracer_flow_root_step_end_semantics():
    tr = Tracer()
    tr.flow(1, 0.5, 0, "a", end=True)      # no root yet: dropped
    tr.flow(1, 0.6, 0, "a", step=True)     # step refuses to become root
    assert tr.events == []
    tr.flow(1, 1.0, 0, "a")                # root
    tr.flow(1, 2.0, 5, "worker", step=True)
    tr.flow(1, 3.0, 0, "b", end=True)
    assert [e["ph"] for e in tr.events] == ["s", "t", "f"]


def test_tracer_buffer_is_bounded():
    tr = Tracer(max_events=2)
    for i in range(5):
        tr.instant("x", float(i), 0, "t")
    assert len(tr.events) == 2
    assert tr.n_dropped == 3


def test_tracer_chrome_export_schema():
    tr = Tracer()
    tr.begin_request(7, "r7 toy", 0.0, args={"workflow": "toy"})
    tr.span("dispatch m", 0.0, 1.5, COORDINATOR_PID, "exec0",
            cat="dispatch", trace=7)
    tr.flow(7, 0.0, COORDINATOR_PID, "exec0")
    tr.span("complete r7", 2.0, 0.0, COORDINATOR_PID, "requests", trace=7)
    tr.flow(7, 2.0, COORDINATOR_PID, "requests", end=True)
    tr.end_request(7, "r7 toy", 2.0)
    obj = tr.to_chrome()
    stats = validate_chrome_trace(obj)
    assert stats["n_slices"] == 2
    assert stats["n_flows"] == 1
    assert stats["n_async"] == 2
    evs = obj["traceEvents"]
    x = next(e for e in evs if e["ph"] == "X" and e["name"] == "dispatch m")
    assert x["ts"] == 0.0 and x["dur"] == pytest.approx(1.5e6)   # in us
    f = next(e for e in evs if e["ph"] == "f")
    assert f["bp"] == "e" and f["id"] == 7
    meta = [e["args"]["name"] for e in evs
            if e["ph"] == "M" and e["name"] == "process_name"]
    assert "coordinator" in meta
    # string tids map to stable per-pid ints with name metadata
    tids = {e["args"]["name"] for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"exec0", "requests"} <= tids


def test_make_tracer_respects_gate(tele_off):
    assert make_tracer() is NULL_TRACER
    assert isinstance(make_tracer(enabled=True), Tracer)
    configure(True)
    assert isinstance(make_tracer(), Tracer)


# --------------------------------------------------------------------------
# end-to-end: traced sim run
# --------------------------------------------------------------------------

def test_traced_sim_run_exports_valid_trace(tmp_path, toy_workflow, tele_on):
    reg = MetricsRegistry()
    sys_ = ServingSystem(n_executors=4, metrics=reg)
    sys_.register(toy_workflow)
    reqs = [sys_.submit("toy_cn", inputs={"seed": i, "prompt": "x"},
                        arrival=i * 0.1, steps=4) for i in range(6)]
    sys_.run()
    assert all(r.status == "done" for r in reqs)
    p = tmp_path / "trace.json"
    sys_.export_trace(str(p))
    stats = validate_chrome_trace(str(p))
    assert stats["n_slices"] > 0
    assert stats["n_flows"] == len(reqs)        # one flow per request
    assert stats["n_async"] == 2 * len(reqs)    # b/e pair per request
    # raw jsonl export round-trips
    jl = tmp_path / "trace.jsonl"
    sys_.export_trace(str(jl), fmt="jsonl")
    lines = [json.loads(l) for l in jl.read_text().splitlines()]
    assert any(e["ph"] == "X" and e["name"].startswith("dispatch")
               for e in lines)
    with pytest.raises(ValueError):
        sys_.export_trace(str(p), fmt="nope")
    # the per-system registry scraped the runtime's attribute counters
    txt = sys_.metrics_text()
    assert "coordinator_n_submitted 6" in txt
    assert "scheduler_n_batches" in txt
    assert "coordinator_queue_delay_seconds_count" in txt


def test_trace_closes_dispatch_spans_on_executor_failure(
        tmp_path, toy_workflow, tele_on):
    """A mid-batch executor failure must still close the open dispatch
    span (first of done/timeout/failure wins) so slices keep nesting."""
    sys_ = ServingSystem(n_executors=3, metrics=MetricsRegistry())
    sys_.register(toy_workflow)
    r = sys_.submit("toy_cn", inputs={"seed": 0, "prompt": "x"}, steps=6)
    sys_.coordinator.fail_executor(1, at=0.5)
    sys_.run()
    assert r.status == "done"
    stats = validate_chrome_trace(sys_.tracer.to_chrome())
    assert stats["n_slices"] > 0
    names = [e["name"] for e in sys_.tracer.events if e["ph"] == "i"]
    assert "executor_fail" in names
    assert not sys_.coordinator._open_batch


# --------------------------------------------------------------------------
# disabled path
# --------------------------------------------------------------------------

def test_disabled_tracer_is_noop(toy_workflow, tele_off):
    sys_ = ServingSystem(n_executors=2)
    assert sys_.tracer is NULL_TRACER
    assert not sys_.tracer.enabled
    sys_.register(toy_workflow)
    r = sys_.submit("toy_cn", inputs={"seed": 0, "prompt": "x"}, steps=4)
    sys_.run()
    assert r.status == "done"
    assert NULL_TRACER.events == []          # shared singleton stayed empty
    assert NULL_TRACER.n_dropped == 0
    with pytest.raises(RuntimeError):
        sys_.export_trace(str("/tmp/never-written.json"))


def test_env_gate_parsing(monkeypatch):
    prev = configure(None)
    try:
        for v in ("", "0", "false", "off", "no", "False", " OFF "):
            monkeypatch.setenv("REPRO_TELEMETRY", v)
            assert not telemetry_enabled()
        for v in ("1", "true", "on", "yes"):
            monkeypatch.setenv("REPRO_TELEMETRY", v)
            assert telemetry_enabled()
    finally:
        configure(prev)


def test_tracing_does_not_change_output_bits():
    """REPRO_TELEMETRY on/off must not perturb the executable plane:
    the same request produces bit-identical images either way."""
    from repro.diffusion import make_basic_workflow

    imgs = []
    for enabled in (False, True):
        prev = configure(enabled)
        try:
            sys_ = ServingSystem(n_executors=2, backend=LocalBackend(),
                                 metrics=MetricsRegistry())
            wf = make_basic_workflow("sd3")
            sys_.register(wf)
            req = sys_.submit(wf.name, inputs={"seed": 0, "prompt": "a fox"},
                              arrival=0.0, steps=3)
            sys_.run()
            assert req.status == "done"
            key = req.ref_key(req.graph.outputs["image"])
            imgs.append(np.asarray(sys_.coordinator.engine.value_of(key)))
        finally:
            configure(prev)
    np.testing.assert_array_equal(imgs[0], imgs[1])


# --------------------------------------------------------------------------
# host spans on the profiler's clock, and the host-clock counters
# --------------------------------------------------------------------------

def _serve_one(metrics=None):
    """One toy-width ``sd3:basic`` request through the executable plane;
    returns (system, image, host seconds of ``run``)."""
    import time

    from repro.diffusion import make_basic_workflow

    sys_ = ServingSystem(n_executors=1, backend=LocalBackend(),
                         metrics=metrics or MetricsRegistry())
    wf = make_basic_workflow("sd3")
    sys_.register(wf)
    req = sys_.submit(wf.name, inputs={"seed": 0, "prompt": "a fox"},
                      arrival=0.0, steps=3)
    t0 = time.perf_counter()
    sys_.run()
    wall = time.perf_counter() - t0
    assert req.status == "done"
    key = req.ref_key(req.graph.outputs["image"])
    return sys_, np.asarray(sys_.coordinator.engine.value_of(key)), wall


def test_host_spans_nest_in_the_profiler_trace(tmp_path):
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    from repro.core.tracing import HOST_SPANS

    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve_one()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    (host,) = [p for p in ProfileData.from_file(path).planes
               if p.name == "/host:CPU"]
    # name -> [(line, start, end, stats)]
    spans = {name: [] for name in HOST_SPANS}
    for i, line in enumerate(host.lines):
        for e in line.events:
            if e.name in spans:
                spans[e.name].append((i, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
    assert all(spans[name] for name in HOST_SPANS), \
        {k: len(v) for k, v in spans.items()}
    # each span lies inside one of the span above it, on the same thread
    for outer, inner in zip(HOST_SPANS, HOST_SPANS[1:]):
        for line, a, b, _ in spans[inner]:
            assert any(ol == line and oa <= a and b <= ob
                       for ol, oa, ob, _ in spans[outer]), (inner, outer)
    assert {s["kind"] for *_, s in spans["coordinator.event"]} >= {"arrival"}
    dispatched = [s for *_, s in spans["coordinator.dispatch"]]
    assert all(set(s) == {"model", "batch_size", "segment_steps"}
               for s in dispatched)
    assert any(s["model"].startswith("segment:") for s in dispatched)


def test_host_spans_do_not_change_output_bits(monkeypatch):
    """With no profile session the spans are inert: the image is the
    same, bit for bit, as with every span replaced by a no-op."""
    import contextlib

    import repro.core.executor as executor_mod
    import repro.core.runtime as runtime_mod

    _, with_spans, _ = _serve_one()
    noop = lambda name, **args: contextlib.nullcontext()
    monkeypatch.setattr(runtime_mod, "host_span", noop)
    monkeypatch.setattr(executor_mod, "host_span", noop)
    _, without, _ = _serve_one()
    np.testing.assert_array_equal(with_spans, without)


def test_control_plane_time_leaves_out_backend_time():
    sys_, _, wall = _serve_one()
    co = sys_.coordinator
    # the backend's own measure (load and execute) lies inside the
    # coordinator's wall time of the backend calls
    assert 0 < co.backend.exec_seconds <= co.backend_time
    assert 0 < co.control_plane_time < co.backend_time
    assert co.control_plane_time + co.backend_time == \
        pytest.approx(wall, rel=0.05)
    assert f"coordinator_backend_time {co.backend_time:g}" \
        in sys_.metrics_text()


def test_queue_delay_observed_on_the_host_clock(monkeypatch, tele_off):
    from repro.core.telemetry import Histogram

    seen = []
    observe = Histogram.observe

    def record(self, v):
        seen.append(v)
        observe(self, v)

    monkeypatch.setattr(Histogram, "observe", record)
    reg = MetricsRegistry()
    sys_, _, wall = _serve_one(reg)
    nodes = sum(len(b.nodes) for b in sys_.coordinator.dispatch_log)
    assert nodes > 0 and len(seen) == nodes
    assert all(0 <= v <= wall for v in seen)
    assert "coordinator_queue_delay_seconds_count" in reg.to_prometheus()

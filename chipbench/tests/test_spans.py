"""Device-idle time put down to the program's host spans, on synthetic
traces, and the program names the benchmark reads, pinned to what the
program lowers."""

import re

import pytest

from chipbench import harness, spans, xplane

E = xplane.Event


def trace(host, ops=((0.0, 1.0), (2.0, 3.0))):
    """A one-device trace whose device is idle between its ``ops``."""
    dev = xplane.Device("/device:TPU:0", [],
                        [E("%fusion.1 = f32[8] fusion", a, b) for a, b in ops])
    return xplane.Trace([dev], [E(n, a, b) for n, a, b in host])


def readings(t, segments=1):
    seg = [harness.Dispatch("segment:sd3", 8, 1)] * segments
    return harness.Readings(window_s=3.0, dispatches=seg, trace=t,
                            geometry=None, architecture=None, peaks={},
                            programs=harness.PROGRAMS,
                            flash_kernel=harness.FLASH_KERNEL)


def backend(t):
    return spans.idle_in(t, t.devices[0], "backend.execute")


def coordinator(t):
    return spans.idle_in(t, t.devices[0], "coordinator.event",
                         outside=("backend.execute",))


def test_gap_half_inside_a_backend_call():
    t = trace([("coordinator.event", 0.0, 3.0),
               ("backend.execute", 1.5, 3.0)])
    assert backend(t) == pytest.approx(0.5)
    assert coordinator(t) == pytest.approx(0.5)


def test_gap_inside_a_coordinator_event_only():
    t = trace([("coordinator.event", 0.0, 3.0),
               ("backend.execute", 0.2, 0.9)])
    assert backend(t) == 0.0
    assert coordinator(t) == pytest.approx(1.0)


def test_gap_outside_every_span():
    t = trace([("coordinator.event", 0.0, 0.9),
               ("backend.execute", 0.1, 0.5), ("ReadSyncFlag", 1.0, 2.0)])
    assert backend(t) == 0.0
    assert coordinator(t) == 0.0


def test_nested_spans_count_once():
    t = trace([("coordinator.event", 0.0, 3.0),
               ("coordinator.event", 1.2, 1.8),
               ("coordinator.event", 1.5, 2.5),
               ("backend.execute", 1.1, 1.3), ("backend.execute", 1.2, 1.4)])
    assert backend(t) == pytest.approx(0.3)
    assert coordinator(t) == pytest.approx(0.7)


def test_names_match_up_to_a_hash():
    t = trace([("backend.execute#model=vae#", 1.0, 1.25),
               ("backend.executed", 1.5, 2.0)])
    assert backend(t) == pytest.approx(0.25)


def test_interval_arithmetic():
    a = [(0.0, 2.0), (3.0, 6.0)]
    b = [(1.0, 4.0), (5.0, 5.5)]
    assert spans.intersect(a, b) == [(1.0, 2.0), (3.0, 4.0), (5.0, 5.5)]
    assert spans.subtract(a, b) == [(0.0, 1.0), (4.0, 5.0), (5.5, 6.0)]
    assert spans.subtract(a, []) == a and spans.intersect(a, []) == []


@pytest.mark.parametrize("cell", ["backlog", "solo"])
def test_metrics_read_ms_per_segment_dispatch(cell):
    t = trace([("coordinator.event", 0.0, 3.0),
               ("backend.execute", 1.5, 3.0)])
    r = readings(t, segments=2)
    assert harness.read_metric(f"idle_in_backend_ms.{cell}", r) == \
        pytest.approx(250.0)
    assert harness.read_metric(f"idle_in_coordinator_ms.{cell}", r) == \
        pytest.approx(250.0)


@pytest.mark.parametrize("metric", ["idle_in_backend_ms.backlog",
                                    "idle_in_coordinator_ms.backlog",
                                    "idle_in_backend_ms.solo",
                                    "idle_in_coordinator_ms.solo"])
def test_a_program_without_spans_reads_nothing(metric):
    t = trace([("ReadSyncFlag", 1.0, 2.0)])
    assert harness.read_metric(metric, readings(t)) is None
    assert harness.read_metric(metric, readings(None)) is None
    assert harness.read_metric(metric, readings(t, segments=0)) is None


def test_program_names_are_what_the_program_lowers():
    """The text encoder, the segment scan and the VAE decode that one
    toy-width request runs lower to modules named as
    ``harness.PROGRAMS`` says, the names the device trace shows."""
    from repro.core import LocalBackend, ServingSystem
    from repro.diffusion import make_basic_workflow

    keys = {"text_encoder": "apply", "segment": "scan", "vae": "decode"}
    backend = LocalBackend()
    system = ServingSystem(n_executors=1, backend=backend)
    system.register(make_basic_workflow("sd3"))
    graph = system.registry.instantiate("sd3:basic", steps=2)
    jitted, calls = {}, {}
    for node in graph.nodes:
        role = node.op.model_id.split(":")[0]
        if role not in keys:
            continue
        comps = backend.ensure_loaded(node.op)[0]
        jitted[role] = comps[keys[role]]

        def record(*args, _role=role, **kw):
            calls.setdefault(_role, (args, kw))
            return jitted[_role](*args, **kw)

        comps[keys[role]] = record
    req = system.submit("sd3:basic", inputs={"seed": 3, "prompt": "a fox"},
                        steps=2)
    system.run()
    assert req.status == "done" and set(calls) == set(keys)
    for role, (args, kw) in calls.items():
        hlo = jitted[role].lower(*args, **kw).compile().as_text()
        assert re.match(r"HloModule ([^\s,]+)", hlo).group(1) == \
            harness.PROGRAMS[role]

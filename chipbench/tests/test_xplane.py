"""The trace reduction on a small trace recorded on a TPU v5e: three runs
each of a jitted flash-attention call (the Pallas kernel, 2 heads x 256
tokens) and of a 512x512 matmul, with a 2 ms host span between them."""

from pathlib import Path

import pytest

from chipbench import xplane

TRACE = Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"
KERNEL = r"^%mha(\.\d+)? = .*tpu_custom_call"


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    return xplane.from_profile(ProfileData.from_file(str(TRACE)))


def test_programs_and_busy_time(trace):
    (dev,) = trace.devices
    assert dev.name == "/device:TPU:0"
    assert [p.name for p in dev.programs] == ["jit__lambda"] * 6
    # busy is the union of the op intervals, inside the programs' spans
    assert 0 < trace.busy_s <= sum(p.seconds for p in dev.programs)
    assert trace.busy_s == pytest.approx(25.379e-6, rel=1e-3)
    seconds, runs = xplane.program_seconds(dev, "jit__lambda")
    assert runs == 6 and seconds == pytest.approx(25.426e-6, rel=1e-3)


def test_kernel_time(trace):
    (dev,) = trace.devices
    seconds, calls = xplane.op_seconds(dev, KERNEL)
    assert calls == 3
    assert seconds == pytest.approx(13.039e-6, rel=1e-3)
    assert xplane.op_seconds(dev, KERNEL, program="jit_run") == (0.0, 0)


def test_top_ops_are_self_times_by_op(trace):
    (dev,) = trace.devices
    top = xplane.top_ops(dev, 3)
    assert top[0][0] == "jit__lambda/mha.1 bf16[2,256,64]"
    assert top[0][1] == pytest.approx(13.039e-6, rel=1e-3)
    assert len(top) == 3
    assert sum(o.self_s for o in dev.ops) <= trace.busy_s * (1 + 1e-9)


def test_gaps_are_named_by_the_host(trace):
    (dev,) = trace.devices
    gaps = xplane.idle_between(dev, "jit__lambda")
    assert len(gaps) == 5 and all(g > 0 for g in gaps)
    named = xplane.named_gaps(trace, dev, n=2)
    # the two longest gaps hold the 2 ms host span the trace was made with
    assert [n for n, _ in named] == ["host_gap_marker", "host_gap_marker"]
    assert all(3e-3 < s < 5e-3 for _, s in named)


def test_self_time_of_a_loop_leaves_out_its_body():
    ops = [xplane.Event("%while.1 = loop", 0.0, 10.0),
           xplane.Event("%fusion.2 = f32[8] fusion", 1.0, 4.0),
           xplane.Event("%mha.3 = custom-call", 5.0, 9.0),
           xplane.Event("%copy.4 = f32[8] copy", 11.0, 12.0)]
    xplane._self_times(ops)
    assert [o.self_s for o in ops] == [3.0, 3.0, 4.0, 1.0]
    assert xplane.union(ops) == [(0.0, 10.0), (11.0, 12.0)]
    assert ops[1].op == "fusion.2 f32[8]"

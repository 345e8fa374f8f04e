"""Device-idle time in which the host was inside a backend call (the
program's ``backend.execute`` span: components, input stacking and
transfers, program launch, the wait and the output split), in ms per
segment dispatch (device trace)."""

from chipbench import spans


def read(r):
    return spans.idle_ms_per_segment(r, "backend.execute")

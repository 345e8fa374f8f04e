"""Device-idle time in which the host was inside one event of the
coordinator's loop (the program's ``coordinator.event`` span) and not in
a backend call, in ms per segment dispatch (device trace)."""

from chipbench import spans


def read(r):
    return spans.idle_ms_per_segment(r, "coordinator.event",
                                     outside=("backend.execute",))

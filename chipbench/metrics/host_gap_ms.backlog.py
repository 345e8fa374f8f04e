"""Mean device-idle time between consecutive DenoiseSegment programs on
the device, in ms (device trace)."""

from chipbench import xplane


def read(r):
    dev = r.device()
    gaps = xplane.idle_between(dev, r.programs["segment"]) if dev else []
    return 1e3 * sum(gaps) / len(gaps) if gaps else None

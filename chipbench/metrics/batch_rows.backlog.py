"""Requests per DenoiseSegment dispatch in the window (program counter:
the coordinator's dispatch log)."""


def read(r):
    seg = r.segment_dispatches()
    return sum(d.batch_size for d in seg) / len(seg) if seg else None

"""Device-to-host copy rate: bytes that crossed (``kernel.d2h`` spans'
``bytes``, padding frames included) over the time the copies took, in GB/s."""


def read(run):
    spans = run.spans_named("kernel.d2h")
    secs = sum(s.t1 - s.t0 for s in spans)
    if secs <= 0:
        return None
    return sum(int(s.attrs["bytes"]) for s in spans) / secs / 1e9

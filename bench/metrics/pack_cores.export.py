"""Cores the host Rice pack keeps busy: CPU seconds of the pack jobs
(``kernel.pack_job`` spans' ``cpu_s``) over the seconds in which at least
one job ran (the union of their intervals)."""
from devtrace import covered


def read(run):
    spans = [s for s in run.spans_named("kernel.pack_job") if "cpu_s" in s.attrs]
    secs = covered((s.t0, s.t1) for s in spans)
    if secs <= 0:
        return None
    return sum(float(s.attrs["cpu_s"]) for s in spans) / secs

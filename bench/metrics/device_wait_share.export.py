"""Share of the window the executor blocks on a chunk's device outputs before
reading them (``kernel.device_wait`` spans), in percent."""


def read(run):
    spans = run.spans_named("kernel.device_wait")
    if not spans:
        return None
    return 100.0 * sum(s.t1 - s.t0 for s in spans) / run.window_s

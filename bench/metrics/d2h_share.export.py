"""Share of the window spent copying device outputs to the host
(``kernel.d2h`` spans), in percent."""


def read(run):
    spans = run.spans_named("kernel.d2h")
    if not spans:
        return None
    return 100.0 * sum(s.t1 - s.t0 for s in spans) / run.window_s

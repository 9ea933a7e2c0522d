"""Share of the window spent in the filter stage (``pipeline.filter``
spans), in percent."""


def read(run):
    spans = run.spans_named("pipeline.filter")
    if not spans:
        return None
    return 100.0 * sum(s.t1 - s.t0 for s in spans) / run.window_s

"""Share of the window spent encoding each result and writing it to the
result lake (``pipeline.lake_put`` spans), in percent."""


def read(run):
    spans = run.spans_named("pipeline.lake_put")
    if not spans:
        return None
    return 100.0 * sum(s.t1 - s.t0 for s in spans) / run.window_s

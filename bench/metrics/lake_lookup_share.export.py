"""Share of the window spent keying each instance and looking it up in the
result lake (``pipeline.lake_lookup`` spans), in percent."""


def read(run):
    spans = run.spans_named("pipeline.lake_lookup")
    if not spans:
        return None
    return 100.0 * sum(s.t1 - s.t0 for s in spans) / run.window_s

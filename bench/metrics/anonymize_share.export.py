"""Share of the window spent anonymizing scrubbed instances and writing
their manifest entries (``pipeline.anonymize`` spans), in percent."""


def read(run):
    spans = run.spans_named("pipeline.anonymize")
    if not spans:
        return None
    return 100.0 * sum(s.t1 - s.t0 for s in spans) / run.window_s

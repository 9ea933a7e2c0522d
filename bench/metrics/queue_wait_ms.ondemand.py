"""Mean wait of a request before the pipeline starts on it: its due time to
the start of its ``pipeline.run_study`` span, found through the work-item key
of the ``worker.process`` span it runs under. That span is an ancestor, not
necessarily the parent (the worker opens ``worker.deid`` in between), so the
reader walks up the span tree to it."""


def read(run):
    if run.cell.traffic["loop"] != "open":
        return None
    due = {r.journal_key: r.due for r in run.requests}
    by_id = {s.span_id: s for s in run.spans}
    waits = []
    for s in run.spans:
        if s.name != "pipeline.run_study":
            continue
        up = by_id.get(s.parent_id)
        while up is not None and up.name != "worker.process":
            up = by_id.get(up.parent_id)
        key = up.attrs.get("key") if up is not None else None
        if key in due:
            waits.append(s.t0 - due[key])
    return 1000.0 * sum(waits) / len(waits) if waits else None

"""Share of the frames whose Golomb-Rice payload the device spliced: the
``kernel.entropy_code`` spans' ``device_packed`` over ``device_packed`` plus
``host_packed``, in percent."""


def read(run):
    spans = [s for s in run.spans_named("kernel.entropy_code") if "device_packed" in s.attrs]
    frames = sum(int(s.attrs["device_packed"]) + int(s.attrs["host_packed"]) for s in spans)
    if not frames:
        return None
    return 100.0 * sum(int(s.attrs["device_packed"]) for s in spans) / frames

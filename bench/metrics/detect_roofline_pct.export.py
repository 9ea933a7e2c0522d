"""The text-band detector kernel's share of its HBM roofline, in percent.

Needed bytes are those of the real frames the window sent through the
detector kernel (from the ``kernel.detect_dispatch`` spans of the
``textdetect`` path): each pixel read once, the least any implementation of
the pass moves. Padding frames count as waste, not work. Time is the device
time of the ``_profiles`` custom calls in the trace."""
from devtrace import kernel_seconds
from peaks import peaks
from roofline import detect_bytes

KERNEL = "_profiles"


def read(run):
    secs = kernel_seconds(run.device_events, KERNEL, run.t0, run.t1)
    spans = [s for s in run.spans_named("kernel.detect_dispatch")
             if s.attrs.get("path") == "textdetect"]
    if secs <= 0 or not spans:
        return None
    need = sum(detect_bytes(int(s.attrs["batch"]), s.attrs["shape"], s.attrs["dtype"])
               for s in spans)
    return 100.0 * need / peaks(run.device_kind)["hbm_bytes_per_s"] / secs

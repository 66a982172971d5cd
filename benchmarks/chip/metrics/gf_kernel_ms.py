"""Device milliseconds of the GF(2^8) Pallas kernel per completed repair,
from the kernel's events in the trace."""
import cb_trace


def read(run):
    t = run.traffic
    if run.summary is None or not t.repairs:
        return None
    s = cb_trace.kernel_seconds(run.summary)
    return s * 1e3 / t.repairs if s else None

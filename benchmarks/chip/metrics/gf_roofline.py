"""Share of its roofline that the GF(2^8) kernel reached: the least time
the chip needs for the window's GF work (counted from each call's own
shapes, cb_roofline) over the kernel's device time in the trace."""
import cb_roofline
import cb_trace


def read(run):
    t = run.traffic
    if run.summary is None or not t.mm_shapes:
        return None
    kernel_s = cb_trace.kernel_seconds(run.summary)
    if not kernel_s:
        return None
    least, bound = cb_roofline.least_time(t.mm_shapes,
                                          cb_roofline.peaks(run.device_kind))
    run.say("gf_roofline", bound=bound, least_s=least, kernel_s=kernel_s,
            calls=len(t.mm_shapes))
    return least / kernel_s * 100.0

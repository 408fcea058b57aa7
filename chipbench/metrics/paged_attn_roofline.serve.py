"""Share of its roofline that the paged attention kernel reached.

The least time is the larger of the useful causal FLOPs and the Q, K, V
and O bytes (the architecture module's ``paged_step``) of every row of
every engine step in the traced window, over the chip's peaks; the time
is the device time of the paged kernel's events in that window.
"""
import peaks
import trace_reduce


def read(data):
    t = data.get("trace")
    steps = data.get("traced_steps")
    if t is None or not steps:
        return None
    kernel_s = t.kernel_s(trace_reduce.PAGED_ATTENTION)
    if kernel_s <= 0:
        return None
    f = b = 0
    for s in steps:
        sf, sb = data["reference"].paged_step(data["sizes"], s["rows"])
        f += sf
        b += sb
    return 100 * peaks.roofline_s(f, b, data["device_kind"]) / kernel_s

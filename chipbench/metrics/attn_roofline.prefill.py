"""Share of its roofline that the prefill's attention kernels reached.

The least time is the larger of causal attention's useful FLOPs over the
bf16 peak and its Q, K, V and O bytes over the HBM bandwidth (the
architecture module's ``prefill_attention``), for the prefills in the
trace; the time is the device time of the attention kernels' events
there.
"""
import peaks
import trace_reduce


def read(data):
    t = data.get("trace")
    if t is None:
        return None
    kernel_s = t.kernel_s(trace_reduce.PREFILL_ATTENTION)
    if kernel_s <= 0:
        return None
    p = data["prefill"]
    f, b = data["reference"].prefill_attention(data["sizes"], p["seq_len"])
    least = peaks.roofline_s(f, b, data["device_kind"]) * p["traced"]
    return 100 * least / kernel_s

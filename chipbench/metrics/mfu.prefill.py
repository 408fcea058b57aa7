"""Model FLOPs of the window's prefills (``flops.prefill``: matmuls of
every layer, causal attention, the last position's unembedding) per
second, over the chip's bf16 peak."""
import flops
import peaks


def read(data):
    p = data.get("prefill")
    if not p or p["elapsed_s"] <= 0:
        return None
    rate = p["count"] * flops.prefill(data["sizes"], p["seq_len"]) \
        / p["elapsed_s"]
    return 100 * rate / peaks.peaks(data["device_kind"])["bf16_flops"]

"""Model FLOPs of the window's prefills (the architecture module's
``prefill_flops``: for a dense model, matmuls of every layer, causal
attention, the last position's unembedding) per second, over the chip's
bf16 peak."""
import peaks


def read(data):
    p = data.get("prefill")
    if not p or p["elapsed_s"] <= 0:
        return None
    ref = data["reference"]
    rate = p["count"] * ref.prefill_flops(data["sizes"], p["seq_len"]) \
        / p["elapsed_s"]
    return 100 * rate / peaks.peaks(data["device_kind"])["bf16_flops"]

"""Model FLOPs the engine computed in the window per second, over the
chip's bf16 peak: every prompt and output token of every step that
started in the window (the architecture module's ``tokens_flops`` at each
row's context), with the unembedding of each sampled token
(``unembed_flops``), over the time from the window opening to the end of
its last step."""
import peaks


def read(data):
    steps = data.get("steps")
    if not steps:
        return None
    sizes, ref = data["sizes"], data["reference"]
    total = 0
    for s in steps:
        for q, kv in s["rows"]:
            total += ref.tokens_flops(sizes, q, kv, unembedded=0)
        total += s["tokens"] * ref.unembed_flops(sizes)
    elapsed = max(s["t1"] for s in steps) - data["window"][0]
    return 100 * total / elapsed / peaks.peaks(data["device_kind"])["bf16_flops"]

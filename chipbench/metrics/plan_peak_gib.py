"""AutoChunk's own predicted activation peak (``final_peak``) of the
chunked block in the timed program, in GiB."""


def read(data):
    peak = data.get("plan_peak_bytes")
    return None if peak is None else peak / 2**30

"""Share of the device's busy time in AutoChunk's chunk I/O: the own time
of the ops under the ``autochunk.io`` scope (the zeroed output buffers,
the slicing in and writing out of each chunk; ``core/lowering.py``), over
the busy time, in the traced window's profiler trace (``scopes.py``).
A trace whose ops carry none of the program's scopes reads nothing."""
import scopes


def read(data):
    if data.get("trace") is None:
        return None
    events = scopes.read_trace(scopes.trace_dir())
    if events is None:
        return None
    times, busy = scopes.scope_times(events)
    own = scopes.by_scope(times)
    if busy <= 0 or not set(own) - {scopes.NONE}:
        return None
    return 100 * own.get(scopes.CHUNK_IO, 0.0) / busy

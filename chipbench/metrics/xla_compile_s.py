"""Host seconds jax spent compiling the timed program with XLA, or loading
it from the persistent compilation cache: the ``jit.compile`` and
``jit.cache_load`` spans (``repro.obs.tracing``) of one jit, over the run.

That jit is the timed program's: the one whose ``jit.trace`` span holds
the AutoChunk compile (the top-level ``compile.*`` spans run while the
model is traced), the outermost such when jits nest.  Its compile spans
are those named ``jit(<traced fun>)`` that start after that trace ends:
the kernel autotuner's candidates, compiled inside the trace, and the
benchmark's own jitted functions (the architecture module's weights and
reference, other names) are left out."""
from repro.obs import tracing


def read(data):
    spans = tracing.TRACER.spans()
    chunk = [s for s in spans if s.name.startswith("compile.")
             and not (s.parent or "").startswith("compile.")]
    traces = [t for t in spans if t.name == "jit.trace" and any(
        t.start <= c.start and c.end <= t.end for c in chunk)]
    if not traces:
        return None
    timed = max(traces, key=lambda t: t.duration)
    fun = "jit({})".format(timed.args["fun"])
    got = [s.duration for s in spans
           if s.name in ("jit.compile", "jit.cache_load")
           and s.args.get("fun") == fun and s.start >= timed.end]
    return sum(got) if got else None

"""The readers of the program's scopes, counters and jit spans, on a small
synthetic trace whose ops carry ``tf_op`` name stacks, on counter
snapshots and on spans recorded on a manual clock."""
import gzip
import json

import pytest

import bench
import scopes
import trace_reduce
from repro.obs import tracing
from repro.obs.clock import ManualClock

LOOP = "jit(<lambda>)/while/body/autochunk/while/body/closed_call"


def reader(name):
    return bench.load_module(bench.HERE / "metrics" / f"{name}.py")


@pytest.mark.parametrize("tf_op,scope", [
    (f"{LOOP}/mlp/dot_general:", "mlp"),
    (f"{LOOP}/attention/bkgqs,bskd->bqkgd/jit(masked_attention)/"
     "masked_attention:", "attention"),
    (f"{LOOP}/autochunk.io/dynamic_update_slice:", "autochunk.io"),
    ("jit(<lambda>)/while/body/autochunk/while:", "autochunk"),
    ("jit(<lambda>)/unembed/dot_general:", "unembed"),
    ("jit(<lambda>)/while/body/norm/mul:", "norm"),
    ("jit(<lambda>)/dot_general:", "none"),
    ("", "none"),
])
def test_scope_of(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def events(scoped=True):
    """One device: a 100 us chunk loop holding 30 us of chunk I/O, 40 us
    of the FFN and 20 us of the attention kernel; then, after a 50 us gap,
    20 us of an unscoped op."""
    meta = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/host:CPU"}},
    ]

    def op(name, ts, dur, tf_op, **extra):
        args = dict(extra, tf_op=tf_op if scoped else "jit(<lambda>)/x:")
        return {"ph": "X", "pid": 1, "tid": 2, "name": name, "ts": ts,
                "dur": dur, "args": args}

    return meta + [
        op("while.3", 0, 100, "jit(<lambda>)/autochunk/while:"),
        op("dynamic_slice_fusion", 0, 10, f"{LOOP}/autochunk.io/ds:"),
        op("convolution_fusion", 10, 40, f"{LOOP}/mlp/dot_general:"),
        op("masked_attention.1", 50, 20,
           f"{LOOP}/attention/jit(masked_attention)/masked_attention:",
           hlo_category="custom-call",
           source="/x/src/repro/kernels/chunked_attention.py:223"),
        op("dynamic_update_slice.2", 70, 20, f"{LOOP}/autochunk.io/dus:"),
        op("copy", 150, 20, "jit(<lambda>)/copy:"),
        # a host event is not a device op
        {"ph": "X", "pid": 9, "tid": 1, "name": "bench.dispatch", "ts": 0,
         "dur": 500},
    ]


def write_trace(root, evs):
    path = root / "plugins" / "profile" / "1" / "vm.trace.json.gz"
    path.parent.mkdir(parents=True)
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": evs}, f)


def test_scope_times_are_own_times_and_busy_the_union():
    times, busy = scopes.scope_times(events())
    own = scopes.by_scope(times)
    assert busy == pytest.approx(120e-6)
    assert own == pytest.approx({"autochunk.io": 30e-6, "mlp": 40e-6,
                                 "attention": 20e-6, "autochunk": 10e-6,
                                 "none": 20e-6})
    # the kernel keeps its source-line name beside its scope
    assert ("attention", "chunked_attention.py:223") in times


@pytest.fixture
def trace_at(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "trace_dir", lambda: tmp_path)
    return tmp_path


def summary():
    return trace_reduce.TraceSummary(window_s=1.0, busy_s=120e-6)


def test_chunk_io_share(trace_at):
    write_trace(trace_at, events())
    got = reader("chunk_io_share.prefill").read({"trace": summary()})
    assert got == pytest.approx(100 * 30 / 120)


def test_chunk_io_share_reads_nothing_without_program_scopes(trace_at):
    read = reader("chunk_io_share.prefill").read
    assert read({"trace": summary()}) is None   # no trace file
    write_trace(trace_at, events(scoped=False))
    assert read({"trace": summary()}) is None   # a program without scopes
    assert read({"trace": None}) is None        # no device ops traced


@pytest.mark.parametrize("counts,want", [
    ({"kernel_dispatch_attention": 4, "kernel_dispatch_computed_mask": 1,
      "kernel_dispatch_bool_mask.mask_eval_limit": 3}, 75.0),
    ({"kernel_dispatch_attention": 2, "kernel_dispatch_computed_mask": 2},
     0.0),
    ({"kernel_dispatch_attention": 0, "kernel_dispatch_computed_mask": 0},
     None),
    ({"kernel_dispatch_hits": 3, "kernel_dispatch_computed_mask": 1}, None),
])
def test_attn_bool_mask_share(counts, want, monkeypatch):
    from repro.core import stats

    monkeypatch.setattr(stats, "snapshot", lambda: dict(counts))
    assert reader("attn_bool_mask_share").read({}) == want


def timed_run_spans(load):
    """Spans of a run: the timed program's trace holds the AutoChunk
    compile, a nested jit and an autotune compile; its lowering and its
    compile (or cache load) follow; then the reference compiles."""
    clk = ManualClock()
    tr = tracing.Tracer(clock=clk)
    tr.record_done("jit.compile", 0.0, fun="jit(make)")     # the weights
    clk.advance(1.0)
    with tr.span("compile.search"):
        clk.advance(1.5)
        tr.record_done("jit.compile", 0.5, fun="jit(<lambda>)")  # autotune
        clk.advance(0.5)
    clk.advance(1.0)
    tr.record_done("jit.trace", 3.5, fun="wrapped")
    clk.advance(0.5)
    tr.record_done("jit.trace", 4.5, fun="<lambda>")
    clk.advance(1.0)
    tr.record_done("jit.lower", 1.0, fun="jit(<lambda>)")
    clk.advance(7.0)
    tr.record_done("jit.cache_load" if load else "jit.compile", 7.0,
                   fun="jit(<lambda>)")
    clk.advance(2.0)
    tr.record_done("jit.compile", 2.0, fun="jit(last_logits)")
    return tr


@pytest.mark.parametrize("load", [False, True])
def test_xla_compile_s(load, monkeypatch):
    monkeypatch.setattr(tracing, "TRACER", timed_run_spans(load))
    assert reader("xla_compile_s").read({}) == pytest.approx(7.0)


def test_xla_compile_s_reads_nothing_without_jit_spans(monkeypatch):
    tr = tracing.Tracer(clock=ManualClock())
    with tr.span("compile.search"):
        pass
    monkeypatch.setattr(tracing, "TRACER", tr)
    assert reader("xla_compile_s").read({}) is None

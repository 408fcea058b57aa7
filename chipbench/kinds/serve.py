"""Open-loop serving on the paged engine.

The engine is the one ``launch/serve.py --paged`` builds:
``serving.PagedServeEngine`` on the cell's pool, with the planner's
prefill chunk.  Requests arrive on the seeded schedule of the cell's mix
at the cell's fixed rate; each is submitted once it is due, stamped with
its due time, and the loop steps the engine while it has work and sleeps
while it has none.

Set-up ends after a pre-roll at the cell's rate (``preroll_s``, about one
request lifetime), so the window opens on a loaded engine.  The window's
requests are those due in the next ``--seconds``; arrivals go on while
they drain, up to ``drain_s`` more.  Token times are read on the host
right after the ``step()`` that produced them.

``correct``: a sample of the window's finished requests drawn from the
seed, the longest among them, with at least ``check_tokens`` served tokens
in all; the plain reference of the configuration's architecture module
(``ctx.reference``) runs once over each prompt with its served
tokens, and ``token_gap`` is the widest gap by which a served token's
logit lies below the reference's largest logit at that position.  With
``ctx.control`` the tokens the float8 reference puts first at those
positions are judged by the same comparison in the program's place.
"""
from __future__ import annotations

import time

import numpy as np

import traffic


class _StepLog:
    """What each engine step did, read from the engine's scheduler state."""

    def __init__(self, engine):
        self.engine = engine
        self.steps = []   # dicts: t0, t1, kind, rows [(q, kv)], tokens

    def step(self, ctx, active):
        e = self.engine
        before = {id(st): (st, st.kv_len) for st in e.running}
        counts = dict(e.sched_stats)
        t0 = time.perf_counter()
        with ctx.annotate("engine.step"):
            e.step()
        t1 = time.perf_counter()
        rows = []
        for st, kv0 in before.values():
            if st.kv_len > kv0:
                rows.append((st.kv_len - kv0, st.kv_len))
        for st in e.running:
            if id(st) not in before and st.kv_len > 0:
                rows.append((st.kv_len, st.kv_len))
        kind = next((k for k in ("mixed_steps", "prefill_steps",
                                 "decode_steps")
                     if e.sched_stats[k] > counts[k]), None)
        tokens = 0
        for r in active:
            n = len(r.generated)
            while len(r.token_times) < n:
                r.token_times.append(t1)
                tokens += 1
        self.steps.append({
            "t0": t0, "t1": t1, "kind": kind, "rows": rows,
            "tokens": tokens,
            "refusals": (e.sched_stats["admission_refusals"]
                         - counts["admission_refusals"]),
        })


def percentile(values, q):
    if not len(values):
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), q))


def build(ctx, num_pages=None):
    """The engine ``launch/serve.py --paged`` builds, on the cell's pool,
    with both step widths compiled."""
    from repro.models import model as M
    from repro.serving import PagedServeEngine, Request

    work = ctx.work
    params = ctx.reference.make(ctx.sizes, ctx.seed, M.param_specs(ctx.cfg))
    engine = PagedServeEngine(
        ctx.cfg, params, max_seqs=work["max_seqs"], max_len=work["max_len"],
        page_size=work["page_size"],
        num_pages=num_pages or work["num_pages"], prefill_chunk="auto",
        autochunk_budget=work.get("autochunk_budget"),
    )
    # warm both step widths: the prompt runs prefill steps at
    # q_max=chunk, its two tokens decode steps at q_max=1
    n = min(engine.prefill_chunk + 1, work["max_len"] - 2)
    engine.submit(Request(rid=-1, max_new_tokens=2, prompt=list(
        traffic.token_ids(ctx.seed, "warm", n, ctx.sizes["vocab_size"]))))
    engine.run()
    engine.finished.clear()
    return engine


class Window:
    """One open-loop pass: arrivals at ``rate`` from now, a pre-roll of
    ``preroll_s``, a window of ``seconds`` and a drain of up to
    ``drain_s``."""

    def __init__(self, ctx, engine, rate, seconds, preroll_s, drain_s):
        self.ctx, self.engine = ctx, engine
        self.rate, self.seconds = rate, seconds
        self.preroll_s, self.drain_s = preroll_s, drain_s
        horizon = preroll_s + seconds + drain_s
        self.arrivals = traffic.schedule(
            ctx.mix, rate, int(np.ceil(rate * horizon)) + 1, ctx.seed)
        self.log = _StepLog(engine)
        self.in_window = []
        self.waiting_at = {}
        self.late = []    # seconds each request was submitted after due

    def run(self, on_open=None, trace_s=None):
        from repro.serving import Request

        ctx, engine, V = self.ctx, self.engine, self.ctx.sizes["vocab_size"]
        T0 = time.perf_counter()
        self.win0 = win0 = T0 + self.preroll_s
        self.win1 = win1 = win0 + self.seconds
        deadline = win1 + self.drain_s
        tracing = False
        active, nxt, opened = [], 0, False
        while True:
            now = time.perf_counter()
            if not opened and now >= win0:
                opened = True
                self.waiting_at["open"] = len(engine.waiting)
                if on_open is not None:
                    on_open()
                if trace_s:
                    self._settle()
                    ctx.start_trace()
                    tracing = True
            if tracing and now >= win0 + trace_s:
                self._settle()
                ctx.stop_trace()
                tracing = False
            if "close" not in self.waiting_at and now >= win1:
                self.waiting_at["close"] = len(engine.waiting)
            while nxt < len(self.arrivals) and \
                    T0 + self.arrivals[nxt].due_s <= now:
                a = self.arrivals[nxt]
                with ctx.annotate("bench.generate"):
                    req = Request(rid=nxt, max_new_tokens=a.output_len,
                                  prompt=list(traffic.token_ids(
                                      ctx.seed, f"req{nxt}", a.prompt_len,
                                      V)))
                req.submitted_at = T0 + a.due_s
                req.token_times = []
                self.late.append(now - req.submitted_at)
                engine.submit(req)
                active.append(req)
                if win0 <= req.submitted_at < win1:
                    self.in_window.append(req)
                nxt += 1
            if now >= win1 and all(r.done for r in self.in_window):
                break
            if now >= deadline:
                break
            if engine.waiting or engine.running:
                self.log.step(ctx, active)
                active = [r for r in active if not r.done]
            else:
                due = (T0 + self.arrivals[nxt].due_s
                       if nxt < len(self.arrivals) else deadline)
                with ctx.annotate("bench.sleep"):
                    time.sleep(max(0.0, min(due, deadline) - now))
        self.t_end = time.perf_counter()
        if tracing:
            self._settle()
            ctx.stop_trace()
        # requests still queued or running are dropped with the engine
        return self

    def _settle(self):
        import jax

        jax.block_until_ready(self.engine.pool.pages)

    def latencies(self):
        """(ttft_s per window request, inter-token gaps in s, unfinished
        count).  A request with no first token counts as waiting until the
        run ended."""
        ttft, gaps, unfinished = [], [], 0
        for r in self.in_window:
            first = r.token_times[0] if r.token_times else self.t_end
            ttft.append(first - r.submitted_at)
            gaps.extend(np.diff(r.token_times))
            unfinished += not r.done
        return ttft, gaps, unfinished

    def window_steps(self):
        return [s for s in self.log.steps if self.win0 <= s["t0"] < self.win1]


def run(ctx) -> dict:
    import bench

    work, sizes = ctx.work, ctx.sizes
    engine = build(ctx)
    win = Window(ctx, engine, work["rate_per_s"], ctx.seconds,
                 work["preroll_s"], work["drain_s"])
    setup = {}
    win.run(on_open=lambda: setup.setdefault("s", ctx.end_setup()),
            trace_s=work["trace_s"] if ctx.trace else None)
    peak = ctx.memory_peak_bytes()
    ttft, gaps, failed = win.latencies()
    ctx.log(f"{len(win.in_window)} requests in the window, {failed}"
            f" unfinished; {len(win.log.steps)} steps; prefill chunk"
            f" {engine.prefill_chunk}; queue {win.waiting_at}; submitted"
            f" late by {np.median(win.late):.4f}s median,"
            f" {np.max(win.late):.4f}s at most")

    done = [r for r in win.in_window if r.done]
    sample = _sample(done, work["check_tokens"], ctx.seed)
    checked = [(list(r.prompt), list(r.generated)) for r in sample]
    chunk = engine.prefill_chunk
    for r in win.in_window:
        r.first_logits = None
    del engine, sample, done
    win.engine = win.log.engine = None
    bench.free_device_state()

    t = time.perf_counter()
    with ctx.annotate("bench.reference"):
        gap, ctl = compare(ctx.reference, sizes, ctx.seed, checked,
                           control=ctx.control)
    n_tok = sum(len(s) for _, s in checked)
    ctx.log(f"reference over {len(checked)} requests, {n_tok} served"
            f" tokens: {time.perf_counter() - t:.2f}s")
    correct, checks = judge(bool(checked), gap, failed, work["limits"])

    layer_data = {
        "kind": "serve",
        "steps": win.window_steps(),
        "window": (win.win0, win.win1),
        "prefill_chunk": chunk,
        "trace": None,
        "traced_steps": [],
    }
    if ctx.trace and ctx.trace_window:
        import trace_reduce

        t0, t1 = ctx.trace_window
        layer_data["traced_steps"] = [s for s in win.log.steps
                                      if t0 <= s["t0"] and s["t1"] <= t1]
        layer_data["trace"] = trace_reduce.reduce_dir(
            ctx.trace_dir, ctx.trace_window)
    res = {
        "correct": correct,
        "attempted": len(win.in_window),
        "failed": failed,
        "end_to_end": {
            "ttft_p95_s": percentile(ttft, 95),
            "itl_p95_ms": 1e3 * percentile(gaps, 95),
            "setup_s": setup["s"],
        },
        "memory_peak_bytes": peak,
        "layer_data": layer_data,
        "checks": checks,
    }
    if ctl is not None:
        ok, ctl_checks = judge(bool(checked), ctl, failed, work["limits"])
        res["control"] = {"correct": ok, "checks": ctl_checks}
    return res


def judge(checked: bool, gap: float, unfinished: int, limits):
    """``correct`` and the numbers compared: the widest token gap over the
    checked requests, and the window's requests left unfinished."""
    limit = limits["token_gap"]
    ok = checked and np.isfinite(gap) and gap <= limit and unfinished == 0
    return bool(ok), [("token_gap", gap, limit), ("unfinished", unfinished, 0)]


def compare(ref, sizes, seed, checked, control=False):
    """Widest gap of the reference ``ref`` below its best logit over the
    served tokens of ``checked`` [(prompt, served)], and with ``control``
    the same for the tokens the float8 control puts first at those
    positions."""
    gap = 0.0
    ctl = 0.0 if control else None
    for prompt, served in checked:
        seq = prompt + served[:-1]
        positions = np.arange(len(prompt) - 1, len(seq))
        lookup = np.asarray(served)[:, None]
        if control:
            _, top, _ = ref.position_stats(sizes, seed, seq, positions,
                                           lookup, mode="fp8",
                                           dtype=sizes["dtype"])
            lookup = np.concatenate([lookup, top[:, None]], axis=1)
        best, _, got = ref.position_stats(sizes, seed, seq, positions,
                                          lookup, dtype=sizes["dtype"])
        gap = max(gap, float(np.max(best - got[:, 0])))
        if control:
            ctl = max(ctl, float(np.max(best - got[:, 1])))
    return gap, ctl


def _sample(done, check_tokens, seed):
    """The longest finished request, then others in an order drawn from
    the seed, until ``check_tokens`` served tokens are covered."""
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.generated))
    rest = [r for r in done if r is not longest]
    order = traffic.rng(seed, "sample").permutation(len(rest))
    picked = [longest]
    n = len(longest.generated)
    for i in order:
        if n >= check_tokens:
            break
        picked.append(rest[i])
        n += len(rest[i].generated)
    return picked

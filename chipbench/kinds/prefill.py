"""Closed-loop long prefill through the AutoChunk'd forward.

The window times ``models.model.next_token_logits`` with the cell's
activation budget set, as one ahead-of-time compiled executable (whose
``memory_analysis()`` gives ``prefill_act_gib``).  A closed loop keeps the
mix's ``in_flight`` prefills dispatched (1 unless the mix says more), each
sent as soon as the oldest one completed, so that the chip stays fed
while the host stands still.  Once ``--seconds`` have passed, nothing more
is sent, every prefill sent is waited for, and the clock is read after
that wait: the rate counts all of that work over all of that time.  A
traced run traces the window's first prefill alone, before the others are
dispatched.

``correct``: the next-token logits of one prefill drawn from the seed,
against the plain reference of the configuration's architecture module
(``ctx.reference``) on the same prompt (``logit_err``: largest
absolute gap over the vocabulary, over the reference's largest absolute
logit).  With ``ctx.control`` the float8 reference's logits are judged by
the same comparison in the program's place.
"""
from __future__ import annotations

import time

import numpy as np

import traffic


def run(ctx) -> dict:
    import jax

    from repro.models import model as M

    import bench

    ctx.install_plan_cache()
    work, mix, sizes, ref = ctx.work, ctx.mix, ctx.sizes, ctx.reference
    cfg = ctx.cfg.with_(autochunk_budget=float(work["autochunk_budget_bytes"]))
    V, S = sizes["vocab_size"], mix["prompt_len"]

    params = ref.make(sizes, ctx.seed, M.param_specs(cfg))
    host_prompts = [traffic.token_ids(ctx.seed, f"prompt{i}", S, V)
                    for i in range(mix["distinct_prompts"])]
    prompts = [jax.device_put(p[None]) for p in host_prompts]
    fn = jax.jit(lambda p, t: M.next_token_logits(cfg, p, {"tokens": t}))
    with ctx.annotate("bench.compile"):
        compiled = fn.lower(params, prompts[0]).compile()
    mem = compiled.memory_analysis()
    jax.block_until_ready(compiled(params, prompts[0]))
    plans = M.autochunk_results(cfg)
    setup_s = ctx.end_setup()
    ctx.log(f"setup {setup_s:.2f}s; temp_size_in_bytes"
            f" {mem.temp_size_in_bytes}; argument_size_in_bytes"
            f" {mem.argument_size_in_bytes}; plan stages"
            f" {[len(r.plan) for r in plans]}")

    in_flight = int(mix.get("in_flight", 1))
    outs, ends, pending = [], [], []

    def send():
        i = len(outs) % len(prompts)
        with ctx.annotate("bench.dispatch"):
            out = compiled(params, prompts[i])
        outs.append((i, out))
        pending.append(out)

    def wait_oldest():
        with ctx.annotate("bench.wait"):
            pending.pop(0).block_until_ready()
        ends.append(time.perf_counter())

    t_first = time.perf_counter()
    if ctx.trace:
        ctx.start_trace()
        send()
        wait_oldest()
        ctx.stop_trace()
    while len(pending) < in_flight:
        send()
    while pending:
        wait_oldest()
        if ends[-1] - t_first < ctx.seconds:
            send()
    elapsed = ends[-1] - t_first
    peak = ctx.memory_peak_bytes()

    count = len(outs)
    pick = int(traffic.rng(ctx.seed, "sample").integers(count))
    idx, got = outs[pick]
    got = np.asarray(got[0, :V], np.float32)
    each = np.diff([t_first] + ends)
    ctx.log(f"{count} prefills of {S} tokens, {in_flight} in flight, in"
            f" {elapsed:.3f}s (between completions"
            f" {', '.join(f'{t:.4f}' for t in each)}s); checking prefill"
            f" {pick} (prompt {idx})")
    del params, prompts, compiled, outs
    bench.free_device_state()

    t = time.perf_counter()
    with ctx.annotate("bench.reference"):
        want = ref.last_logits(sizes, ctx.seed, host_prompts[idx],
                               dtype=sizes["dtype"])
    ctx.log(f"reference {time.perf_counter() - t:.2f}s")
    limits = work["limits"]
    correct, checks = judge(got, want, limits)
    control = None
    if ctx.control:
        ctl = ref.last_logits(sizes, ctx.seed, host_prompts[idx], mode="fp8",
                              dtype=sizes["dtype"])
        ok, ctl_checks = judge(ctl, want, limits)
        control = {"correct": ok, "checks": ctl_checks}

    layer_data = {
        "kind": "prefill",
        "prefill": {"count": count, "seq_len": S, "elapsed_s": elapsed,
                    "traced": 1 if ctx.trace else 0},
        "plan_peak_bytes": max((r.final_peak for r in plans), default=None),
        "trace": None,
    }
    if ctx.trace:
        import trace_reduce

        layer_data["trace"] = trace_reduce.reduce_dir(
            ctx.trace_dir, ctx.trace_window)
    res = {
        "correct": correct,
        "attempted": count,
        "failed": 0,
        "end_to_end": {
            "prefill_tokens_per_s": count * S / elapsed,
            "prefill_act_gib": mem.temp_size_in_bytes / 2**30,
            "setup_s": setup_s,
        },
        "memory_peak_bytes": peak,
        "layer_data": layer_data,
        "checks": checks,
    }
    if control is not None:
        res["control"] = control
    return res


def judge(got, want, limits):
    """``correct`` and the numbers compared, for next-token logits ``got``
    against the reference's ``want``."""
    err = logit_err(got, want)
    limit = limits["logit_err"]
    return bool(np.isfinite(err) and err <= limit), [("logit_err", err, limit)]


def logit_err(got, want) -> float:
    """Largest absolute gap over the reference's largest absolute logit."""
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

"""A run with the timed path broken underneath reads ``correct`` false:
one test for each fault a cell can have on one chip.  (Batch 1 prefill
has no batch to halve, and no cell has an exchange between chips.)"""
import functools

import jax.numpy as jnp
import pytest

from conftest import run_cell


def test_prefill_answer_altered(cell_root, on_cpu, monkeypatch):
    """The next-token logits come out shifted by one token."""
    from repro.models import model as M

    real = M.next_token_logits

    def altered(*a, **kw):
        return jnp.roll(real(*a, **kw), 1, axis=-1)

    monkeypatch.setattr(M, "next_token_logits", altered)
    out = run_cell(on_cpu, cell_root, "tiny.prefill")
    assert out["correct"] is False
    c = out["checks"]["logit_err"]
    assert c["value"] > c["limit"]


def broken_step(kind):
    """Wrap the engine's step program so that it returns its KV pool
    unchanged (``state``), or shifts every row's logits by one token, so
    that the token sampled from them is altered where it is made
    (``token``)."""
    from repro.serving import PagedServeEngine

    real = PagedServeEngine._step_fn

    @functools.wraps(real)
    def step_fn(self, q_max):
        fn = real(self, q_max)

        def broken(params, pages, *rest):
            logits, new_pages = fn(params, pages, *rest)
            if kind == "state":
                return logits, pages
            return jnp.roll(logits, 1, axis=-1), new_pages

        return broken

    return step_fn


@pytest.mark.parametrize("kind", ["state", "token"])
def test_serve_fault(cell_root, on_cpu, monkeypatch, kind):
    from repro.serving import PagedServeEngine

    monkeypatch.setattr(PagedServeEngine, "_step_fn", broken_step(kind))
    out = run_cell(on_cpu, cell_root, "tiny.serve")
    assert out["correct"] is False
    c = out["checks"]["token_gap"]
    assert c["value"] > c["limit"]

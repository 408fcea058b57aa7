"""The control: the plain reference in float8, put in the program's
place, must read ``correct`` false by the same comparison that reads the
program's output true.  At the cells' own size this is read on the chip
by ``control.py``; here at a reduced size on the CPU, on three seeds."""
import pytest

from conftest import run_cell

SEEDS = [3, 2**31 + 17, 123456789]


@pytest.mark.parametrize("cell,check", [("tiny.prefill", "logit_err"),
                                        ("tiny.serve", "token_gap")])
def test_control_fails_where_the_program_passes(cell_root, on_cpu, cell,
                                                check):
    for seed in SEEDS:
        out = run_cell(on_cpu, cell_root, cell, seed=seed, control=True)
        assert out["correct"] is True
        assert out["control"]["correct"] is False
        c, ctl = out["checks"][check], out["control"]["checks"][check]
        assert ctl["limit"] == c["limit"]
        assert ctl["value"] > c["limit"]
        assert ctl["value"] > 3 * c["value"]
        assert list(out)[-1] == "checks"


def test_benchmark_runs_never_read_the_control(cell_root, on_cpu):
    out = run_cell(on_cpu, cell_root, "tiny.prefill")
    assert "control" not in out

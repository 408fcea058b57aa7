"""Each driver end to end on the CPU at a reduced size, through
``bench.run``, with the chip gate replaced inside the test."""
import json

import pytest

from conftest import MIXES, run_cell, write_json

BIG_SEED = 2**31 + 4242


def test_refuses_a_cpu(cell_root):
    import bench

    with pytest.raises(bench.ChipMissing, match="no TPU"):
        run_cell(bench, cell_root, "tiny.prefill")


def test_main_prints_no_result_off_the_chip(capsys):
    import bench

    assert bench.main(["--workload", "phi3-mini-3.8b.prefill-32k", "--seed",
                       "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("in_flight", [1, 3])
@pytest.mark.parametrize("trace", [0, 1])
def test_prefill(cell_root, on_cpu, trace, in_flight):
    write_json(cell_root / "traffic" / "tiny-prefill.json",
               dict(MIXES["tiny-prefill"], in_flight=in_flight))
    out = run_cell(on_cpu, cell_root, "tiny.prefill", seed=BIG_SEED,
                   trace=trace)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= in_flight
    assert list(out)[-1] == "checks"
    c = out["checks"]["logit_err"]
    assert c["value"] <= c["limit"]
    m = out["metrics"]
    if trace:
        assert {"autochunk_compile_s", "plan_peak_gib", "mfu.prefill"} \
            <= set(m)
        assert m["plan_peak_gib"]["unit"] == "GiB"
    else:
        assert set(m) == {"prefill_tokens_per_s", "prefill_act_gib",
                          "setup_s"}
        assert m["prefill_tokens_per_s"]["value"] > 0
        assert m["prefill_act_gib"]["value"] > 0
    json.dumps(out)


@pytest.mark.parametrize("trace", [0, 1])
def test_serve(cell_root, on_cpu, trace):
    out = run_cell(on_cpu, cell_root, "tiny.serve", seed=BIG_SEED,
                   trace=trace)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 3
    c = out["checks"]["token_gap"]
    assert c["value"] <= c["limit"]
    m = out["metrics"]
    if trace:
        assert {"autochunk_compile_s", "mfu.serve", "decode_step_ms.serve",
                "mixed_step_ms.serve", "admit_refusal_share.serve"} <= set(m)
        assert 0 < m["mfu.serve"]["value"] < 100
    else:
        assert set(m) == {"ttft_p95_s", "itl_p95_ms", "setup_s"}
        assert m["itl_p95_ms"]["value"] > 0
        assert m["ttft_p95_s"]["value"] > 0
    json.dumps(out)

"""A new cell, traffic mix, configuration, architecture or per-layer
metric is a new file, found by its name; no existing file changes."""
import hashlib
import json

import pytest

from conftest import (CELLS, CONFIG, MIXES, bench_json, run_cell,
                      write_json)

READER = '''"""A test metric: the number of prefills in the window."""


def read(data):
    p = data.get("prefill")
    return None if p is None else p["count"]
'''


# A second architecture module: the dense decoder with its weights drawn
# under another key, so that a tree made by another module's ``make``
# fails its reference.  Each call is appended to ``salted.calls``.
SALTED = '''"""The dense decoder, its weights drawn under a salted seed."""
from pathlib import Path

from references import dense

CALLS = Path(__file__).with_suffix(".calls")
SALT = 0x5EED


def _logged(name, fn):
    def call(*args, **kwargs):
        with open(CALLS, "a") as f:
            f.write(name + "\\n")
        return fn(*args, **kwargs)
    return call


make = _logged("make", lambda sizes, seed, spec: dense.make(
    sizes, seed ^ SALT, spec))
last_logits = _logged("last_logits", lambda sizes, seed, *a, **kw:
                      dense.last_logits(sizes, seed ^ SALT, *a, **kw))
position_stats = _logged("position_stats", lambda sizes, seed, *a, **kw:
                         dense.position_stats(sizes, seed ^ SALT, *a, **kw))
prefill_flops = _logged("prefill_flops", dense.prefill_flops)
prefill_attention = _logged("prefill_attention", dense.prefill_attention)
tokens_flops = _logged("tokens_flops", dense.tokens_flops)
unembed_flops = _logged("unembed_flops", dense.unembed_flops)
paged_step = _logged("paged_step", dense.paged_step)
'''


def digests(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()}


def test_new_files_are_found_by_name(cell_root):
    import bench

    before = digests(cell_root)
    write_json(cell_root / "configs" / "tiny2.json", CONFIG)
    write_json(cell_root / "traffic" / "tiny-prefill-long.json",
               dict(MIXES["tiny-prefill"], prompt_len=512))
    write_json(cell_root / "workloads" / "tiny2.prefill-long.json",
               dict(CELLS["tiny.prefill"], config="tiny2",
                    traffic="tiny-prefill-long"))
    (cell_root / "metrics" / "prefill_count.py").write_text(READER)
    assert all(digests(cell_root)[p] == d for p, d in before.items())

    files = bench.cell_files("tiny2.prefill-long", cell_root)
    assert files["mix"]["prompt_len"] == 512
    assert files["config"]["sizes"] == CONFIG["sizes"]
    bench_file = json.loads((cell_root / "BENCHMARK.json").read_text())
    bench_file["per_layer"].append(
        {"name": "prefill_count", "unit": "1", "better": "higher",
         "source": "host_clock", "layer": "test", "moves": "setup_s",
         "workloads": ["tiny2.prefill-long"]})
    entries = bench.cell_metrics(bench_file, "tiny2.prefill-long",
                                 "per_layer")
    assert "prefill_count" in [m["name"] for m in entries]
    got = bench.read_layer_metrics(
        [m for m in entries if m["name"] == "prefill_count"],
        {"prefill": {"count": 3}}, cell_root)
    assert got == {"prefill_count": {"value": 3.0, "unit": "1"}}


def test_new_architecture_is_new_files(cell_root, on_cpu, monkeypatch):
    """A configuration of another architecture joins as its module, its
    configuration, traffic and cell; the prefill driver then runs on that
    module's weights, reference and counts."""
    before = digests(cell_root)
    (cell_root / "references" / "salted.py").write_text(SALTED)
    write_json(cell_root / "configs" / "tiny-salted.json",
               dict(CONFIG, reference="salted"))
    monkeypatch.setitem(MIXES, "tiny-prefill-salted", MIXES["tiny-prefill"])
    write_json(cell_root / "traffic" / "tiny-prefill-salted.json",
               MIXES["tiny-prefill-salted"])
    cell = dict(CELLS["tiny.prefill"], config="tiny-salted",
                traffic="tiny-prefill-salted")
    write_json(cell_root / "workloads" / "tiny-salted.prefill.json", cell)
    # the cell's entry goes into a copy of the benchmark file, so that no
    # existing file changes
    added = cell_root / "BENCHMARK.added.json"
    real = json.loads((on_cpu.ROOT / "BENCHMARK.json").read_text())
    write_json(added, bench_json(dict(CELLS, **{"tiny-salted.prefill": cell}),
                                 real))
    args = on_cpu.parse_args(["--workload", "tiny-salted.prefill", "--seed",
                              "11", "--seconds", "2", "--trace", "1"])
    out = on_cpu.run(args, root=cell_root, bench_file=added)
    assert all(digests(cell_root)[p] == d for p, d in before.items())

    # the salted weights pass only under the salted reference
    assert out["correct"] is True
    assert "mfu.prefill" in out["metrics"]
    calls = (cell_root / "references" / "salted.calls").read_text().split()
    assert {"make", "last_logits", "prefill_flops"} <= set(calls)


def test_every_configuration_names_a_whole_module():
    import bench

    real = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    for c in real["configs"]:
        conf = json.loads((bench.ROOT / c["file"]).read_text())
        mod = bench.reference(conf)
        assert all(callable(getattr(mod, f)) for f in bench.CONTRACT)


@pytest.mark.parametrize("module,match", [
    (None, "no architecture module"),
    ("short", r"lacks \['paged_step'\]"),
])
def test_bad_module_fails_before_anything_compiles(cell_root, on_cpu,
                                                   monkeypatch, module,
                                                   match):
    """A configuration naming a missing module, or one that lacks a
    function of the contract, stops the run before the chip is asked for
    and before anything is traced or compiled."""
    name = "nowhere"
    if module is not None:
        name = module
        text = SALTED.split("paged_step =")[0]
        (cell_root / "references" / f"{name}.py").write_text(text)
    write_json(cell_root / "configs" / "tiny.json",
               dict(CONFIG, reference=name))

    def reached(chips):
        raise AssertionError("the run went on to the chip")

    monkeypatch.setattr(on_cpu, "require_chip", reached)
    with pytest.raises(ValueError, match=match):
        run_cell(on_cpu, cell_root, "tiny.prefill")


def test_reader_with_nothing_to_read_is_left_out(cell_root):
    import bench

    (cell_root / "metrics" / "prefill_count.py").write_text(READER)
    entry = {"name": "prefill_count", "unit": "1"}
    assert bench.read_layer_metrics([entry], {}, cell_root) == {}


def test_every_listed_metric_has_a_reader():
    import bench

    real = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    for m in real["per_layer"]:
        assert (bench.HERE / "metrics" / f"{m['name']}.py").is_file()
    for w in real["workloads"]:
        files = bench.cell_files(w["name"])
        assert files["work"]["config"] == w["config"]
        assert files["work"]["traffic"] == w["traffic"]
        assert (bench.HERE / "kinds" / f"{files['mix']['kind']}.py") \
            .is_file()


def test_configuration_files_match_the_program():
    import bench

    real = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    for c in real["configs"]:
        check_configuration(bench, c["name"])


def check_configuration(bench, name):
    conf = json.loads((bench.HERE / "configs" / f"{name}.json").read_text())
    cfg = bench.model_config(conf)
    assert cfg.sliding_window is None
    for change, match in [({"d_ff": 1}, "d_ff"),
                          ({"head_dim": 1}, "head_dim"),
                          ({"rms_norm_eps": 1e-5},
                           r"has no \['rms_norm_eps'\]")]:
        bad = dict(conf, sizes=dict(conf["sizes"], **change))
        with pytest.raises(ValueError, match=match):
            bench.model_config(bad)


@pytest.mark.parametrize("n_experts", [60, 64])
def test_non_dense_keys_are_checked(n_experts):
    """Every key of ``sizes`` is held to the program's configuration, not
    only the dense ones: the registry's qwen2-moe-a2.7b has 60 experts."""
    import bench

    conf = {"registry": "qwen2-moe-a2.7b",
            "sizes": {"family": "moe", "n_experts": n_experts,
                      "n_shared_experts": 4, "experts_per_token": 4,
                      "moe_d_ff": 1408}}
    if n_experts == 60:
        assert bench.model_config(conf).n_experts == 60
    else:
        with pytest.raises(ValueError, match="n_experts"):
            bench.model_config(conf)

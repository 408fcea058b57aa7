"""A new cell, traffic mix, configuration or per-layer metric is a new
file, found by its name; no existing file changes."""
import hashlib
import json

import pytest

from conftest import CELLS, CONFIG, MIXES, write_json

READER = '''"""A test metric: the number of prefills in the window."""


def read(data):
    p = data.get("prefill")
    return None if p is None else p["count"]
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
    bad = dict(conf, sizes=dict(conf["sizes"], d_ff=1))
    with pytest.raises(ValueError, match="d_ff"):
        bench.model_config(bad)

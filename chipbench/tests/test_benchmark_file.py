"""``BENCHMARK.json`` keeps to the limits its format sets."""
import json
import re

import bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source",
                   "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"},
}


def load():
    return json.loads((bench.ROOT / "BENCHMARK.json").read_text())


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_names_units_and_lines():
    b = load()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["chipbench"]
    assert 1 <= b["run_seconds"] <= 51
    assert all(one_line(w) for w in b["command"])
    for group, keys in KEYS.items():
        names = [e["name"] for e in b[group]]
        assert len(names) == len(set(names))
        for e in b[group]:
            assert set(e) <= keys and NAME.match(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e and group != "end_to_end" and group != "per_layer":
                    assert one_line(e[k])
    for w in b["workloads"]:
        assert w["chips"] == 1 and NAME.match(w["traffic"])
    for c in b["configs"]:
        assert (bench.ROOT / c["file"]).is_file()
        assert c["file"].startswith("chipbench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for m in b["per_layer"]:
        assert one_line(m["layer"])
    assert len(json.dumps(b)) < 64 * 1024


def test_every_cell_reports_setup_another_metric_and_a_layer():
    b = load()
    for w in b["workloads"]:
        e2e = [m["name"] for m in bench.cell_metrics(b, w["name"],
                                                      "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = bench.cell_metrics(b, w["name"], "per_layer")
        assert layer
        for m in layer:
            assert m["moves"] in e2e


def test_bounds():
    for m in load()["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25

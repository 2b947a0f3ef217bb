"""``BENCHMARK.json`` and the files it names: every cell's
configuration and traffic file, every per-layer metric's reader, and
every per-layer metric reported where its end-to-end metric is."""
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


def test_files_named_by_the_spec_exist():
    for c in SPEC["configs"]:
        with open(os.path.join(os.path.dirname(BENCH), c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in SPEC["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            driver = json.load(f)["driver"]
        for end in (".py", ".limits.json"):
            assert os.path.exists(os.path.join(BENCH, "drivers",
                                               driver + end))
    for m in SPEC["per_layer"]:
        path = os.path.join(BENCH, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("m", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert callable(mod.read)


def test_per_layer_cells_report_what_they_move():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", cells)


def test_every_cell_reports_setup_and_another_metric():
    for w in SPEC["workloads"]:
        names = [m["name"] for m in SPEC["end_to_end"]
                 if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in names and len(names) >= 2
        assert any(w["name"] in m["workloads"] for m in SPEC["per_layer"])

"""The benchmark finds every piece of every cell by name, and its names
and units keep to the characters the benchmark's contract allows."""
import importlib
import json
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_resolves(cell):
    from bench import compare, model
    cfg = model.load(cell["config"])
    assert cfg["name"] == cell["config"]
    traffic = json.loads((ROOT / "bench" / "traffic" /
                          f"{cell['traffic']}.json").read_text())
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    assert callable(driver.run)
    assert compare.limits(cell["config"], traffic["driver"])
    for m in BENCH["per_layer"]:
        if cell["name"] in m.get("workloads", [cell["name"]]):
            family = m["name"].partition(".")[0]
            assert callable(importlib.import_module(
                f"bench.metrics.{family}").read)


def test_names_and_units():
    named = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
             + BENCH["per_layer"])
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    names = [e["name"] for e in named]
    assert len(names) == len(set(names))


def test_every_per_layer_metric_moves_a_reported_metric():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", [cell])


def test_configs_keep_published_widths():
    from bench import model
    s3dis = model.load("pointnet2_ssg_seg_s3dis")
    scannet = model.load("pointnet2_ssg_seg_scannet")
    for cfg in (s3dis, scannet):
        assert [s["mlp"] for s in cfg["sa_stages"]] == [
            [32, 32, 64], [64, 64, 128], [128, 128, 256], [256, 256, 512]]
        assert [s["radius"] for s in cfg["sa_stages"]] == [0.1, 0.2, 0.4,
                                                            0.8]
        assert cfg["fp_mlp"] == [[256, 256], [256, 256], [256, 128],
                                 [128, 128, 128]]
        from bench import reference
        sizes = reference.stage_sizes(cfg["num_points"], cfg["sa_stages"])
        assert sizes[1:] == [s["npoint"] for s in cfg["sa_stages"]]
    assert (s3dis["num_points"], s3dis["num_classes"]) == (4096, 13)
    assert (scannet["num_points"], scannet["num_classes"]) == (8192, 21)


WIDTH = re.compile(r"(_dim|_rank|hidden|intermediate|latent|state|"
                   r"projection|channels|head|expansion|mlp|widths?)$")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_what_is_reduced(entry):
    from bench import model
    cfg = model.load(entry["name"])
    assert cfg["reduced"] == entry["reduced"]
    assert not [k for k in entry["reduced"] if WIDTH.search(k)]
    for key in entry["reduced"]:
        assert key in cfg["assumed"], key

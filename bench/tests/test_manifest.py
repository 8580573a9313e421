"""``BENCHMARK.json`` against the files it names and its own rules."""
import json
import re

import _paths  # noqa: F401
import pytest

from bench import harness

ROOT = _paths.ROOT
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for p in MANIFEST["paths"]:
        assert (ROOT / p).is_dir() and ".." not in p.split("/")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist(cell):
    w = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    (c,) = [c for c in MANIFEST["configs"] if c["name"] == w["config"]]
    assert (ROOT / c["file"]).is_file()
    assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    assert (ROOT / "bench" / "checks" / f"{cell}.json").is_file()
    assert w["chips"] in (1, 4)


def test_workload_entries_carry_only_the_manifest_keys():
    """A cell on N chips is N one-chip replicas behind the Router: the
    number is its ``chips``; a workload entry has no key of its own for
    it, since the manifest's format allows none."""
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for cell in CELLS:
        c = harness.load_cell(cell, MANIFEST)
        assert c.chips in (1, 4)
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(
        1, len(CELLS) // 2)


def test_every_config_has_a_cell():
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))


def test_names_and_units():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + \
        [c["name"] for c in MANIFEST["configs"]] + \
        [w["traffic"] for w in MANIFEST["workloads"]] + \
        [w["config"] for w in MANIFEST["workloads"]]
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for group in (metrics, MANIFEST["workloads"], MANIFEST["configs"]):
        assert len({g["name"] for g in group}) == len(group)


def test_per_layer_moves_an_end_to_end_metric_of_each_cell():
    for m in MANIFEST["per_layer"]:
        for cell in m["workloads"]:
            c = harness.load_cell(cell, MANIFEST)
            assert m["moves"] in {e["name"] for e in c.end_to_end}, \
                (m["name"], cell)


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_setup_another_metric_and_a_layer(cell):
    c = harness.load_cell(cell, MANIFEST)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert harness.reader(m["name"]).read    # a reader exists


def test_shares_of_a_peak_are_named_for_it():
    for m in MANIFEST["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in CELLS:
        names = {m["name"].split(".")[0]
                 for m in harness.load_cell(w, MANIFEST).per_layer}
        assert "step_mfu_pct" in names

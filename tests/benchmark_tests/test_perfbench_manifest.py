"""BENCHMARK.json holds to the benchmark's contract, and the checker that
says so catches what the contract refuses."""
import json
import os

import pytest

from benchmark.manifest import Manifest, ManifestError

from perfbench_tiny import REPO, add_held_out, copy_benchmark, edit_json


def test_the_repo_manifest_holds_to_the_contract():
    m = Manifest(REPO)
    assert m.problems() == []
    assert sorted(m.data["paths"]) == ["benchmark", "tests/benchmark_tests"]
    assert sum(w["chips"] == 4 for w in m.data["workloads"]) == 1
    assert len(m.data["end_to_end"]) - 1 <= 4        # besides setup_s
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_a_held_out_cell_is_out_of_the_manifest_and_fits_back_in(tmp_path):
    """``benchmark/held_out/`` keeps a cell's entries as they would stand in
    ``BENCHMARK.json``; the repo's file names none of them, and with them
    added it still holds to the contract."""
    held = {}
    for name in os.listdir(os.path.join(REPO, "benchmark", "held_out")):
        with open(os.path.join(REPO, "benchmark", "held_out", name)) as f:
            held[name] = json.load(f)
    assert "resnet50-serve.json" in held
    bench = Manifest(REPO).data
    listed = {e["name"] for g in ("workloads", "end_to_end", "per_layer")
              for e in bench[g]}
    for body in held.values():
        assert body["why_held_out"] and body["to_admit"]
        names = {e["name"] for g in ("workloads", "end_to_end", "per_layer")
                 for e in body[g]}
        assert names and not names & listed
    whole = Manifest(add_held_out(copy_benchmark(tmp_path)))
    assert whole.problems() == []
    assert "resnet50-serve" in [w["name"] for w in whole.data["workloads"]]


@pytest.mark.parametrize("held_out", [False, True])
def test_every_cell_names_files_that_exist_and_load(held_out, tmp_path):
    m = Manifest(add_held_out(copy_benchmark(tmp_path)) if held_out else REPO)
    for w in m.data["workloads"]:
        cfg, mix = m.config(w["config"]), m.mix(w["traffic"])
        assert hasattr(m.module("families", cfg["family"]), "build")
        assert hasattr(m.module("traffic", mix["kind"]), "run")
        assert mix["trace_seconds"] > 0
        assert {"source", "reduced", "assumed", "reference"} <= set(cfg)
        for metric in m.metrics_for("per_layer", w["name"]):
            assert callable(m.module("layer_metrics", metric["name"]).read)


def _break(root, how):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        data = json.load(f)
    how(data)
    with open(path, "w") as f:
        json.dump(data, f)


BREAKS = {
    "second four-chip cell": lambda d: d["workloads"][0].update(chips=4),
    "bound over a tenth": lambda d: d["end_to_end"][0].update(bound=0.2),
    "bound under a hundredth": lambda d: d["end_to_end"][0].update(bound=0.001),
    "no setup_s": lambda d: d.update(end_to_end=[
        m for m in d["end_to_end"] if m["name"] != "setup_s"]),
    "name used twice": lambda d: d["per_layer"][0].update(name="rows_per_s"),
    "bad name": lambda d: d["workloads"][0].update(name="-x y"),
    "metric without a reader": lambda d: d["per_layer"][0].update(name="nope"),
    "unknown key": lambda d: d.update(extra=1),
    "path leaves the repo": lambda d: d.update(paths=["../x"]),
    "run_seconds too long": lambda d: d.update(run_seconds=52),
    "config used by no cell": lambda d: d["configs"].append(
        dict(d["configs"][0], name="orphan", file="benchmark/peaks.json")),
    "pair twice": lambda d: d["workloads"].append(
        dict(d["workloads"][0], name="again")),
    "moves a metric the cell lacks": lambda d: d["per_layer"][0].update(
        moves="images_per_s"),
    "e2e read from the program": lambda d: d["end_to_end"][0].update(
        source="program_counter"),
    "why too long": lambda d: d["workloads"][0].update(why="x" * 201),
    "unit with a star": lambda d: d["end_to_end"][0].update(
        unit="rows*iter/s"),
    "unit over 16 characters": lambda d: d["per_layer"][0].update(
        unit="rows.iterations/s"),
    "no unit": lambda d: d["per_layer"][0].update(unit=""),
    "reduced differs from the file": lambda d: d["configs"][0].update(
        reduced=[]),
}


@pytest.mark.parametrize("what", sorted(BREAKS))
def test_the_checker_refuses(what, tmp_path):
    root = copy_benchmark(tmp_path)
    assert Manifest(root).problems() == []
    _break(root, BREAKS[what])
    assert Manifest(root).problems(), what


def test_a_missing_traffic_file_or_cell_is_an_error(tmp_path):
    root = copy_benchmark(tmp_path)
    os.remove(os.path.join(root, "benchmark/workloads/bulk_table.json"))
    assert any("bulk_table" in p for p in Manifest(root).problems())
    with pytest.raises(ManifestError):
        Manifest(root).cell("no-such-cell")
    edit_json(root, "benchmark/configs/resnet50-bf16-224.json", family="none")
    assert any("family" in p for p in Manifest(root).problems())

"""A later PR adds a configuration, a traffic mix and a per-layer metric as
new files and new entries, and edits no file that is there: a trainer cell
with metrics of its own, and a decode cell that joins the decode readers by
its name appended to their cells."""
import hashlib
import json
import os
import shutil

import pytest

from benchmark.harness import run_cell
from benchmark.manifest import Manifest

from perfbench_tiny import TINY_GBDT, tiny_root
from test_perfbench_causal_lm import tiny_lm_root
from test_perfbench_host_phases import NINE, assert_the_manifest_lists_the_nine
from test_perfbench_overlap import (DECODE_CELLS, METRIC,
                                    assert_the_manifest_lists_the_metric)


def _digests(root):
    out = {}
    for dirpath, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            path = os.path.join(dirpath, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha1(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(autouse=True)
def _leave_the_process_as_found():
    import jax
    from mmlspark_tpu.parallel import get_active_mesh, set_active_mesh
    mesh = get_active_mesh()
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    set_active_mesh(mesh)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


def test_a_new_cell_needs_new_files_and_entries_only(tmp_path):
    root = tiny_root(tmp_path)
    before = _digests(root)

    # a new configuration: a narrower, deeper trainer
    cfg = dict(TINY_GBDT, family="gbdt", source="a test", features=6,
               params={"objective": "binary", "max_depth": 3, "max_bin": 63,
                       "learning_rate": 0.2, "seed": 0},
               label_noise=0.3, reduced=[], assumed={})
    with open(os.path.join(root, "benchmark/configs/gbdt-narrow.json"),
              "w") as f:
        json.dump(cfg, f)
    # a new traffic mix: data for the general back-to-back generator
    with open(os.path.join(root, "benchmark/workloads/refit_many.json"),
              "w") as f:
        json.dump({"kind": "back_to_back", "rate_metric": "rows_per_s",
                   "label_flips": 8, "at_least": 3, "trace_seconds": 1}, f)
    # a new per-layer metric: a small reader of its own
    with open(os.path.join(root, "benchmark/layer_metrics/gbdt.fits.py"),
              "w") as f:
        f.write('"""Fits the window ran."""\n\n\n'
                'def read(run):\n    return run.facts.get("fits")\n')
    # and a reader that finds nothing to read is left out of the line
    with open(os.path.join(root, "benchmark/layer_metrics/gbdt.none.py"),
              "w") as f:
        f.write('def read(run):\n    return None\n')

    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "gbdt-narrow", "source": "a test",
                             "file": "benchmark/configs/gbdt-narrow.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "gbdt-narrow-refit",
                               "config": "gbdt-narrow",
                               "traffic": "refit_many", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "rows_per_s":
            m["workloads"].append("gbdt-narrow-refit")
    for name in ("gbdt.fits", "gbdt.none"):
        bench["per_layer"].append({
            "name": name, "unit": "fits", "better": "higher",
            "source": "host_clock", "layer": "gbdt_driver",
            "moves": "rows_per_s", "workloads": ["gbdt-narrow-refit"]})
    with open(path, "w") as f:
        json.dump(bench, f)

    assert Manifest(root).problems() == []
    plain = run_cell(root, "gbdt-narrow-refit", 0, 0.5, False, platform="cpu")
    traced = run_cell(root, "gbdt-narrow-refit", 0, 0.5, True, platform="cpu")
    assert plain["correct"] and plain["attempted"] >= 3
    assert set(plain["metrics"]) == {"rows_per_s", "setup_s"}
    assert traced["correct"], traced
    assert traced["metrics"]["gbdt.fits"]["value"] == traced["attempted"]
    assert "gbdt.none" not in traced["metrics"]
    assert "compile.in_window" in traced["metrics"]    # the shared ones too
    # no file the benchmark had was touched
    after = _digests(root)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "benchmark/configs/gbdt-narrow.json",
        "benchmark/layer_metrics/gbdt.fits.py",
        "benchmark/layer_metrics/gbdt.none.py",
        "benchmark/workloads/refit_many.json"]


def test_a_new_decode_cell_joins_the_decode_readers_by_its_name(tmp_path):
    """The next model on the decode path: its configuration and traffic as
    new files, its name appended to the cells of every entry that lists the
    three decode cells, one new per-layer entry at the end of ``per_layer``
    with its reader, and no file that is there edited."""
    root = tiny_lm_root(tmp_path)
    before = _digests(root)
    cell, config, traffic = "tiny-lm-backlog", "tiny-lm-b", "backlog_b"
    shutil.copy(os.path.join(root, "benchmark/configs/gpt2-xl-bf16.json"),
                os.path.join(root, f"benchmark/configs/{config}.json"))
    shutil.copy(os.path.join(root, "benchmark/workloads/generate_backlog.json"),
                os.path.join(root, f"benchmark/workloads/{traffic}.json"))
    with open(os.path.join(
            root, "benchmark/layer_metrics/decode.steps_in_window.py"),
            "w") as f:
        f.write('"""Step programs the window ran."""\n\n\n'
                'def read(run):\n'
                '    return run.counter("mmlspark_runner_decode_steps_total")\n')

    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    reduced = Manifest(root).config("gpt2-xl-bf16")["reduced"]
    bench["configs"].append({"name": config, "source": "a test",
                             "file": f"benchmark/configs/{config}.json",
                             "reduced": reduced, "why": "a test"})
    bench["workloads"].append({"name": cell, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "a test"})
    joined = []
    for m in bench["end_to_end"] + bench["per_layer"]:
        if set(DECODE_CELLS) <= set(m.get("workloads", [])):
            m["workloads"].append(cell)
            joined.append(m["name"])
    bench["per_layer"].append({
        "name": "decode.steps_in_window", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "tokens_per_s", "workloads": [cell]})
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)

    manifest = Manifest(root)
    assert manifest.problems() == []
    assert {"tokens_per_s", METRIC, *NINE[:5]} <= set(joined)
    assert_the_manifest_lists_the_metric(manifest)
    assert_the_manifest_lists_the_nine(manifest)
    traced = run_cell(root, cell, seed=2**31 + 41, seconds=1.0, trace=True,
                      platform="cpu")
    assert traced["correct"] is True, traced
    assert traced["metrics"]["decode.steps_in_window"]["value"] > 0
    # the decode readers it joined read it too, the device's own aside
    assert {METRIC, *NINE[:5]} <= set(traced["metrics"])
    after = _digests(root)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        f"benchmark/configs/{config}.json",
        "benchmark/layer_metrics/decode.steps_in_window.py",
        f"benchmark/workloads/{traffic}.json"]

"""Helpers for the benchmark's tests: a temp copy of the benchmark with tiny
sizes, which the tests run in-process on the CPU."""
import glob
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_GBDT = dict(rows=4000, block_rows=2000, features=10, holdout_rows=1000,
                 iterations_per_fit=4,
                 expect_path={"hist_backend": "scatter", "quantized": False,
                              "chunk": 1},
                 reference={"holdout_accuracy": 0.88, "tolerance": 0.08})


def edit_json(root, rel, **changes):
    """Merge ``changes`` into the JSON file ``rel`` under ``root``."""
    path = os.path.join(root, rel)
    with open(path) as f:
        data = json.load(f)
    for key, value in changes.items():
        if isinstance(value, dict) and isinstance(data.get(key), dict):
            data[key].update(value)
        else:
            data[key] = value
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
    return data


def copy_benchmark(dst):
    """``BENCHMARK.json`` and ``benchmark/`` copied under ``dst``."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    return str(dst)


def add_held_out(root):
    """Add to the copy's ``BENCHMARK.json`` the cells that
    ``benchmark/held_out/*.json`` keeps out of the repo's, as a later
    benchmark PR would: their entries appended, nothing else touched."""
    bench = edit_json(root, "BENCHMARK.json")
    for path in sorted(glob.glob(os.path.join(root, "benchmark", "held_out",
                                              "*.json"))):
        with open(path) as f:
            held = json.load(f)
        for group in ("workloads", "end_to_end", "per_layer"):
            bench[group] += held[group]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root


def tiny_root(dst):
    """A copy whose every cell, held out or not, runs in seconds on the
    CPU."""
    root = add_held_out(copy_benchmark(dst))
    edit_json(root, "benchmark/configs/gbdt-binary-wide200.json", **TINY_GBDT)
    edit_json(root, "benchmark/configs/gbdt-binary-wide200-dp4.json",
              **dict(TINY_GBDT, rows=8000))
    edit_json(root, "benchmark/configs/resnet50-bf16-224.json",
              image_size=32, batch_size=4, dtype="float32",
              reference={"relative_l2_tolerance": 1e-3})
    edit_json(root, "benchmark/workloads/refit_loop.json", trace_seconds=1)
    edit_json(root, "benchmark/workloads/bulk_table.json", images=8,
              trace_seconds=1)
    edit_json(root, "benchmark/workloads/http_single_poisson.json",
              rate_per_s=20, pool_size=8, sample_requests=6, connections=2,
              slice_seconds=0.5, trace_seconds=1)
    return root

"""Every kind of traffic once, at a tiny size, in-process on the CPU, through
the argument only the tests pass; the shape of the result line; and that the
command line has no way onto the CPU."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import run as run_cli
from benchmark.harness import run_cell

from perfbench_tiny import REPO, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def _leave_the_process_as_found():
    """``run_cell`` is written for a process of its own: it narrows the
    program's mesh to the cell's chips and takes the floor off the compile
    cache.  Other test files share this process."""
    import jax
    from mmlspark_tpu.parallel import get_active_mesh, set_active_mesh
    mesh = get_active_mesh()
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    set_active_mesh(mesh)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


def _check_line(result, cell, manifest, traced):
    """The contract's last line: keys, metric shape, device stamp."""
    line = json.loads(json.dumps(result))          # it must survive JSON
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] >= 1
    group = "per_layer" if traced else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in manifest.metrics_for(group, cell)}
    assert line["metrics"], "no metric reported"
    for name, m in line["metrics"].items():
        assert m["unit"] == allowed[name]
        assert isinstance(m["value"], float) and m["value"] == m["value"]
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    assert "memory_peak_bytes" in dev and "kind" in dev
    if traced:
        assert 0 < dev["busy_s"] <= dev["window_s"] * 1.001
        b = line["breakdown"]
        assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in b["device_ops"])
        assert line["metrics"]["compile.in_window"]["value"] == 0
        assert "rebuilds.in_window" in line["metrics"]
    else:
        assert "breakdown" not in line
        assert set(line["metrics"]) == set(allowed)
        assert line["metrics"]["setup_s"]["value"] > 0
    return line


CASES = [("gbdt-train-1chip", False), ("gbdt-train-1chip", True),
         ("gbdt-train-dp4", False), ("resnet50-bulk", True),
         ("resnet50-serve", False), ("resnet50-serve", True)]


@pytest.mark.parametrize("cell,traced", CASES)
def test_a_tiny_cell_runs_and_prints_the_contracts_line(root, cell, traced):
    from benchmark.manifest import Manifest
    result = run_cell(root, cell, seed=1, seconds=1.0, trace=traced,
                      platform="cpu")
    line = _check_line(result, cell, Manifest(root), traced)
    m = line["metrics"]
    if cell.startswith("gbdt") and not traced:
        assert m["rows_per_s"]["value"] > 0 and line["attempted"] >= 2
    if (cell, traced) == ("gbdt-train-1chip", True):
        assert m["gbdt.device_ms_per_iter"]["value"] > 0
        assert m["binning_s"]["value"] > 0
        assert "gbdt_iter_roofline" not in m     # no peaks: not a device
    if cell == "resnet50-bulk":
        assert 0 < m["featurizer.host_share"]["value"] < 100
        assert m["runner.device_ms_per_batch"]["value"] > 0
        assert "resnet50_fwd_roofline" not in m
    if cell != "gbdt-train-dp4" and traced:
        assert m["rebuilds.in_window"]["value"] == 0
    if (cell, traced) == ("resnet50-serve", False):
        assert 0 < m["p50_ms"]["value"] <= m["p95_ms"]["value"]
        assert line["attempted"] == 20           # 20/s for one second
    if (cell, traced) == ("resnet50-serve", True):
        assert m["serve.p99_ms"]["value"] > 0
        assert m["serve.batch_rows_mean"]["value"] >= 1


def test_the_sharded_cell_rebuilds_what_its_configuration_says_and_no_more(
        root, tmp_path):
    """What the dp4 cell shows on any backend: ``train(shard_rows=True)``
    makes a new jitted objective per call, so two programs are built again
    in every fit of the window.  The configuration says so; a build it does
    not cover fails the run, although the compile cache held the program."""
    from perfbench_tiny import edit_json
    result = run_cell(root, "gbdt-train-dp4", seed=2, seconds=1.0, trace=True,
                      platform="cpu")
    m = result["metrics"]
    assert result["correct"] and m["compile.in_window"]["value"] == 0
    assert m["rebuilds.in_window"]["value"] == 2 * result["attempted"]
    strict = tiny_root(tmp_path)
    edit_json(strict, "benchmark/configs/gbdt-binary-wide200-dp4.json",
              rebuilds_per_operation=0)
    result = run_cell(strict, "gbdt-train-dp4", seed=2, seconds=1.0,
                      trace=True, platform="cpu")
    assert result["correct"] is False
    assert result["metrics"]["compile.in_window"]["value"] == \
        result["metrics"]["rebuilds.in_window"]["value"] == \
        2 * result["attempted"]
    assert any("built inside the measured window" in f
               for f in result["failures"])


def test_a_wrong_path_or_a_wrong_answer_is_not_correct(tmp_path):
    from perfbench_tiny import edit_json
    root = tiny_root(tmp_path)
    edit_json(root, "benchmark/configs/gbdt-binary-wide200.json",
              expect_path={"hist_backend": "matmul"},
              reference={"holdout_accuracy": 0.5, "tolerance": 0.01})
    result = run_cell(root, "gbdt-train-1chip", 0, 0.5, False, platform="cpu")
    assert result["correct"] is False
    assert any("hist_backend" in f for f in result["failures"])
    assert any("accuracy" in f for f in result["failures"])


def test_the_harness_runs_on_a_tpu_only_and_on_enough_chips(root):
    with pytest.raises(RuntimeError, match="runs on 'tpu'"):
        run_cell(root, "gbdt-train-1chip", 0, 1.0, False)
    import jax
    from perfbench_tiny import edit_json
    bench = edit_json(root, "BENCHMARK.json")
    many = len(jax.devices()) + 1
    dp4 = next(w for w in bench["workloads"] if w["name"] == "gbdt-train-dp4")
    dp4["chips"] = many
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    try:
        with pytest.raises(RuntimeError, match=f"needs {many} chips"):
            run_cell(root, "gbdt-train-dp4", 0, 1.0, False, platform="cpu")
    finally:
        dp4["chips"] = 4
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)


def test_the_command_prints_no_result_without_a_tpu_and_has_no_cpu_switch():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("MMLSPARK_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
           "--workload", "resnet50-bulk", "--seed", "0", "--seconds", "1",
           "--trace", "0"]
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=120, cwd=REPO)
    assert p.returncode != 0 and "no result" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
    # an MMLSPARK_TPU_* switch in the environment is refused outright
    p = subprocess.run(cmd, env=dict(env, MMLSPARK_TPU_GBDT_CHUNK="8"),
                       capture_output=True, text=True, timeout=120, cwd=REPO)
    assert p.returncode == 2 and "MMLSPARK_TPU_GBDT_CHUNK" in p.stderr
    # the only arguments are the contract's four
    ap_help = subprocess.run([sys.executable, cmd[1], "--help"], env=env,
                             capture_output=True, text=True, timeout=60)
    flags = {w for w in ap_help.stdout.split() if w.startswith("--")}
    assert {f.rstrip(",") for f in flags} <= {
        "--help", "--workload", "--seed", "--seconds", "--trace"}
    assert run_cli.ROOT == REPO

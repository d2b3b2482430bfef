"""Device seconds by program phase, read from the scope paths of a trace: a
hand-made trace whose answers are known, the slice recorded on the chip, and
the nine per-layer metrics as new entries over files nobody edited."""
import os
import types

import pytest

from benchmark import phase_times as pt
from benchmark import trace_reduce as tr
from benchmark.harness import TRACE_DIR
from benchmark.manifest import Manifest

from perfbench_tiny import REPO, tiny_root

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
S = 1_000_000_000            # one second in ns
STAT = pt.SCOPE_STATS[0]
NEW = ["gbdt.grad_ms_per_iter", "gbdt.quantize_ms_per_iter",
       "gbdt.layout_ms_per_iter", "gbdt.hist_ms_per_iter",
       "gbdt.split_ms_per_iter", "gbdt.route_ms_per_iter",
       "gbdt.update_ms_per_iter", "gbdt.allreduce_ms_per_iter",
       "gbdt.unscoped_share"]


def _xspace(planes):
    """``{plane: {line: [(name, scope path, start_ns, dur_ns), ...]}}`` as a
    serialized xspace; a scope path goes on the event's metadata, under the
    stat the TPU profiler uses."""
    from jax.profiler import ProfileData
    text = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        keys = sorted({e[:2] for evs in lines.values() for e in evs})
        ids = {k: i for i, k in enumerate(keys, 1)}
        text.append(f'planes {{ id: {pid} name: "{pname}"')
        for lid, (lname, events) in enumerate(lines.items(), 1):
            text.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 1000')
            for name, scope, s, d in events:
                text.append(f"events {{ metadata_id: {ids[(name, scope)]} "
                            f"offset_ps: {s * 1000} duration_ps: {d * 1000} }}")
            text.append("}")
        for (name, scope), i in ids.items():
            stat = f' stats {{ metadata_id: 1 str_value: "{scope}" }}' \
                if scope else ""
            text.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{name}"{stat} }} }}')
        text.append(f'stat_metadata {{ key: 1 value {{ id: 1 name: "{STAT}" '
                    f'}} }} }}')
    return ProfileData.text_proto_to_serialized_xspace("\n".join(text))


def _profile(planes):
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(_xspace(planes))


def _reduce(planes, prefix="gbdt."):
    return pt.reduce_chips(pt.device_ops(_xspace(planes)), prefix)


J = "jit(multi)/while/body/closed_call"
TWO_CHIPS = {
    "/device:TPU:0": {
        "XLA Ops": [
            # a while over scoped children keeps only what they leave
            ("while.1", "jit(multi)/while", 0, 10 * S),
            ("fusion.1", f"{J}/gbdt.grad/mul", 1 * S, 1 * S),
            ("sort.2", f"{J}/L0/gbdt.hist/gbdt.layout/sort", 2 * S, 2 * S),
            ("fusion.3", f"{J}/L0/gbdt.hist/while/body/dot_general", 4 * S, 1 * S),
            # the last name of the vocabulary on the path wins
            ("all-reduce.4", f"{J}/L1/gbdt.hist/gbdt.allreduce/psum", 5 * S, 2 * S),
            ("fusion.5", f"{J}/L1/gbdt.route/gather", 7 * S, 1 * S),
            # an operation the compiler made carries no path
            ("copy.6", "", 8 * S, 1 * S)],
        # other lines of the chip are not operations
        "XLA Modules": [("jit_multi", f"{J}/gbdt.hist", 0, 10 * S)],
        "Async XLA Ops": [("copy-start.1", f"{J}/gbdt.hist", 0, 10 * S)]},
    "/device:TPU:1": {
        "XLA Ops": [
            ("while.1", "jit(multi)/while", 0, 10 * S),
            ("fusion.1", f"{J}/gbdt.grad/mul", 1 * S, 3 * S),
            ("fusion.5", f"{J}/L1/gbdt.route/gather", 7 * S, 1 * S)]},
    "/host:CPU": {"main": [("fit", f"{J}/gbdt.hist", 0, 20 * S)]},
}


def test_the_file_is_read_as_profiledata_reads_it_and_with_the_metadata_s_stats():
    """``ProfileData`` shows an event's own stats alone; the profiler keeps
    an instruction's path on the metadata its events share, or, where a
    string repeats, as a reference from there to a stat metadata's name."""
    from jax.profiler import ProfileData
    (chip0, chip1) = pt.device_ops(_xspace(TWO_CHIPS))
    assert [(t, p) for t, p, _, _ in chip0][2] == (
        "sort.2", f"{J}/L0/gbdt.hist/gbdt.layout/sort")
    assert [(t, s, e) for t, _, s, e in chip1] == [
        ("while.1", 1000.0, 1000.0 + 10 * S), ("fusion.1", 1000.0 + 1 * S,
                                               1000.0 + 4 * S),
        ("fusion.5", 1000.0 + 7 * S, 1000.0 + 8 * S)]
    (event,) = [e for plane in _profile(TWO_CHIPS).planes
                if plane.name == "/device:TPU:0" for line in plane.lines
                if line.name == "XLA Ops" for e in line.events
                if e.name == "sort.2"]
    assert list(event.stats) == []
    assert (event.start_ns, event.duration_ns) == (1000.0 + 2 * S, 2.0 * S)
    # a reference is followed to the name it points at; other stats are passed
    blob = ProfileData.text_proto_to_serialized_xspace(f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 5
    events {{ metadata_id: 1 offset_ps: 1000 duration_ps: 2000
             stats {{ metadata_id: 2 uint64_value: 3 }} }}
    events {{ metadata_id: 2 offset_ps: 4000 duration_ps: 1000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.9"
    stats {{ metadata_id: 2 uint64_value: 12 }}
    stats {{ metadata_id: 1 str_value: "jit(f)/gbdt.split/max" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "fusion.10"
    stats {{ metadata_id: 1 ref_value: 7 }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "{STAT}" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "flops" }} }}
  stat_metadata {{ key: 7 value {{ id: 7 name: "jit(f)/gbdt.route/gather" }} }}
}}''')
    assert pt.device_ops(blob) == [[
        ("fusion.9", "jit(f)/gbdt.split/max", 6.0, 8.0),
        ("fusion.10", "jit(f)/gbdt.route/gather", 9.0, 10.0)]]


def test_the_recorded_trace_reads_the_same_through_both_readers():
    from jax.profiler import ProfileData
    path = os.path.join(FIXTURES, "gbdt-train-1chip.first-250ms.xplane.pb")
    (theirs,) = tr._device_ops(ProfileData.from_file(path), "tpu")
    with open(path, "rb") as f:
        (ours,) = pt.device_ops(f.read())
    assert len(ours) == len(theirs) == 103
    for (text, scope, s, e), (name, s0, e0) in zip(ours, theirs):
        assert text == name and scope == ""
        assert (s, e) == pytest.approx((s0, e0), abs=1e-3)


@pytest.mark.parametrize("path,phase,level", [
    ("jit(multi)/while/body/closed_call/L3/gbdt.hist/dot_general",
     "gbdt.hist", "L3"),
    ("jit(f)/L0/gbdt.hist/gbdt.layout/sort", "gbdt.layout", "L0"),
    ("jit(f)/L2/gbdt.split/gbdt.hist/mul", "gbdt.hist", "L2"),
    ("L1/gbdt.hist/gbdt.layout/add", "gbdt.layout", "L1"),
    ("jit(f)/gbdt.grad/L0x/logistic", "gbdt.grad", None),
    ("jit(multi)/while", None, None),
    ("", None, None),
])
def test_a_scope_path_is_booked_to_its_last_phase_and_its_level(path, phase,
                                                                level):
    assert pt.book(path, "gbdt.") == (phase, level)


def test_a_two_chip_trace_gives_the_seconds_one_can_work_out_by_hand():
    found = _reduce(TWO_CHIPS)
    assert found["chips"] == 2
    # mean over the chips: grad (1 + 3) / 2, route (1 + 1) / 2, the rest on
    # chip 0 alone
    assert found["phases"] == pytest.approx({
        "gbdt.grad": 2.0, "gbdt.layout": 1.0, "gbdt.hist": 0.5,
        "gbdt.allreduce": 1.0, "gbdt.route": 1.0})
    assert found["levels"] == pytest.approx({"L0": 1.5, "L1": 2.0})
    # the while's own time (2 s and 6 s) and the bare copy (1 s)
    assert found["unscoped_s"] == pytest.approx((2 + 1 + 6) / 2)
    assert found["total_s"] == pytest.approx(10.0)
    assert sum(found["phases"].values()) + found["unscoped_s"] == \
        pytest.approx(found["total_s"])
    assert found["kinds"]["gbdt.layout"] == pytest.approx({"sort.2": 1.0})
    assert found["kinds"][pt.UNSCOPED] == pytest.approx(
        {"while.1": 4.0, "copy.6": 0.5})
    # the harness's own reduction of the same trace adds up to the same
    summary = tr.reduce_profile(_profile(TWO_CHIPS), (0, 10 * S))
    assert sum(summary.op_seconds.values()) == pytest.approx(found["total_s"])


def test_no_device_plane_or_no_scope_gives_nothing():
    assert _reduce({"/host:CPU": {"main": [("fit", "", 0, S)]}}) is None
    # a program without the scopes, as the parent commit is
    bare = {"/device:TPU:0": {"XLA Ops": [
        ("fusion.1", "jit(multi)/while/body/mul", 0, S), ("copy.2", "", S, S)]}}
    assert _reduce(bare) is None
    assert _reduce(bare, prefix="jit(") is not None


class _Run:
    """What ``by_phase`` reads of a ``harness.Run``."""

    def __init__(self, root, blob, op_seconds, iterations=4):
        self.manifest = types.SimpleNamespace(
            path=lambda *parts: os.path.join(root, *parts))
        self.cell = {"name": "gbdt-train-dp4"}
        self.facts = {"iterations": iterations}
        self.trace_summary = types.SimpleNamespace(op_seconds=op_seconds)
        self.notes = []
        where = os.path.join(root, TRACE_DIR, "gbdt-train-dp4", "plugins",
                             "profile", "2026_09_30")
        os.makedirs(where)
        with open(os.path.join(where, "host.xplane.pb"), "wb") as f:
            f.write(blob)

    def note(self, text):
        self.notes.append(text)


def test_the_readers_give_milliseconds_an_iteration_and_say_what_they_found(
        tmp_path):
    run = _Run(str(tmp_path), _xspace(TWO_CHIPS), {"a": 4.0, "b": 6.0})
    found = pt.by_phase(run)
    assert found["total_s"] == pytest.approx(10.0)
    assert pt.by_phase(run) is found                  # parsed once a file
    # the earlier lines: phases, levels, each phase's largest kinds
    assert len(run.notes) == 2 + len(found["kinds"])
    assert "gbdt.grad 2.0000" in run.notes[0] and "45.00%" in run.notes[0]
    assert run.notes[1].endswith("L0 1.5000, L1 2.0000")
    assert "largest kinds under gbdt.allreduce: all-reduce.4 1.0000" in run.notes
    m = Manifest(REPO)
    values = {name: m.module("layer_metrics", name).read(run) for name in NEW}
    assert values == pytest.approx({
        "gbdt.grad_ms_per_iter": 500.0, "gbdt.layout_ms_per_iter": 250.0,
        "gbdt.hist_ms_per_iter": 125.0, "gbdt.allreduce_ms_per_iter": 250.0,
        "gbdt.route_ms_per_iter": 250.0, "gbdt.unscoped_share": 45.0,
        # a phase that ran no operation has nothing to report
        "gbdt.quantize_ms_per_iter": None, "gbdt.split_ms_per_iter": None,
        "gbdt.update_ms_per_iter": None})


def test_a_total_that_disagrees_with_the_harness_s_reduction_gives_nothing(
        tmp_path):
    run = _Run(str(tmp_path), _xspace(TWO_CHIPS), {"a": 10.0 * 1.006})
    assert pt.by_phase(run) is None
    assert len(run.notes) == 1 and "not the same window" in run.notes[0]
    assert all(Manifest(REPO).module("layer_metrics", n).read(run) is None
               for n in NEW)
    # within the tolerance it stands
    ok = _Run(str(tmp_path / "ok"), _xspace(TWO_CHIPS), {"a": 10.0 * 1.004})
    assert pt.by_phase(ok)["total_s"] == pytest.approx(10.0)


def test_without_a_trace_there_is_nothing_to_read(tmp_path):
    run = _Run(str(tmp_path), _xspace(TWO_CHIPS), {"a": 10.0})
    run.trace_summary = None
    assert pt.by_phase(run) is None and run.notes == []


def test_the_recorded_chip_slice_gives_its_pinned_phases():
    """A quarter second of the traced window of this PR's first
    ``gbdt-train-1chip`` run on a v5e (5M x 200 rows), from 1.75 s after its
    first operation, cut with its scope paths by ``benchmark/tools/
    cut_scoped.py``: the last level of an iteration ends (histogram blocks
    under their ``while``, split, routing), the scores are updated, and the
    next iteration begins with the layout of its root level."""
    with open(os.path.join(
            FIXTURES, "gbdt-train-1chip.scoped-250ms.xplane.pb"), "rb") as f:
        (ops,) = pt.device_ops(f.read())
    assert len(ops) == 5191
    assert ("jit(multi)/while/body/closed_call/L4/gbdt.route/gather:" in
            {path for _, path, _, _ in ops})         # as the profiler spells it
    found = pt.reduce_chips([ops])
    assert found["chips"] == 1
    assert found["total_s"] == pytest.approx(0.25, abs=1e-9)
    assert found["phases"] == pytest.approx({
        "gbdt.layout": 0.135054019, "gbdt.route": 0.062817789,
        "gbdt.hist": 0.044354740, "gbdt.update": 0.006729074,
        "gbdt.split": 0.000077428}, abs=1e-8)
    assert found["unscoped_s"] == pytest.approx(0.000966951, abs=1e-8)
    assert sum(found["phases"].values()) + found["unscoped_s"] == \
        pytest.approx(found["total_s"])
    levels = found["levels"]
    assert levels["L4"] == pytest.approx(0.107242133, abs=1e-8)
    assert levels["L0"] == pytest.approx(0.135055231, abs=1e-8)
    # the scheduler hoists a few microseconds of the other levels' writes
    assert all(levels[k] < 1e-5 for k in ("L1", "L2", "L3"))
    top = {phase: max(kinds, key=kinds.get)
           for phase, kinds in found["kinds"].items()}
    assert top == {"gbdt.layout": "fusion s32[2]",
                   "gbdt.route": "fusion u8[5000000]",
                   "gbdt.hist": "fusion s32[1,200,48,16]",
                   "gbdt.update": "fusion pred[5000000]",
                   "gbdt.split": "fusion (tuple)",
                   pt.UNSCOPED: "while (tuple)"}
    # the same slice through the harness's reduction adds up to the same
    summary = tr.reduce_profile(_fixture_profile(), (0, 1e18))
    assert sum(summary.op_seconds.values()) == pytest.approx(found["total_s"])


def _fixture_profile():
    from jax.profiler import ProfileData
    return ProfileData.from_file(os.path.join(
        FIXTURES, "gbdt-train-1chip.scoped-250ms.xplane.pb"))


def test_the_nine_metrics_are_entries_and_the_manifest_holds():
    # by name, not by place: a later PR appends its own entries after them
    m = Manifest(REPO)
    assert m.problems() == []
    nine = [e for e in m.data["per_layer"] if e["name"] in NEW]
    assert [e["name"] for e in nine] == NEW
    for e in nine:
        assert e["source"] == "device_trace" and e["better"] == "lower"
        assert e["moves"] == "rows_per_s"
        assert "roofline" not in e["name"] and "mfu" not in e["name"]


def test_a_tiny_cpu_cell_runs_and_its_traced_line_lacks_the_new_metrics(
        tmp_path):
    """The CPU backend's events carry no scope path, so the readers find
    nothing and the line leaves the nine out; the accepted ones stay."""
    import jax
    from benchmark.harness import run_cell
    from mmlspark_tpu.parallel import get_active_mesh, set_active_mesh
    root = tiny_root(tmp_path)
    mesh = get_active_mesh()
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        traced = run_cell(root, "gbdt-train-1chip", 3, 1.0, True,
                          platform="cpu")
    finally:
        set_active_mesh(mesh)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    assert traced["correct"], traced
    assert not set(NEW) & set(traced["metrics"])
    assert traced["metrics"]["gbdt.device_ms_per_iter"]["value"] > 0
    listed = {e["name"] for e in Manifest(root).metrics_for(
        "per_layer", "gbdt-train-1chip")}
    assert set(NEW) - {"gbdt.allreduce_ms_per_iter"} <= listed

"""The reduction from a profiler trace to numbers: interval arithmetic, a
hand-made trace whose answers are known, and the trace recorded on the chip."""
import os

import pytest

from benchmark import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


# ------------------------------------------------------ interval arithmetic

def test_union_total_subtract_and_gaps():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9), (20, 30)])
    assert u == [(0, 3), (5, 8), (20, 30)]
    assert tr.total(u) == 16
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == \
        [(0, 2), (4, 8), (22, 29)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]
    assert tr.subtract([(0, 10)], [(0, 10)]) == []
    assert tr.gaps(u, 0, 32) == [(3, 5), (8, 20), (30, 32)]


# ------------------------------------------------- a trace with known answers

def _trace(planes):
    """``{plane: {line: [(name, start_ns, dur_ns), ...]}}`` as ProfileData."""
    from jax.profiler import ProfileData
    text = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        names = sorted({e[0] for evs in lines.values() for e in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        text.append(f'planes {{ id: {pid} name: "{pname}"')
        for lid, (lname, events) in enumerate(lines.items(), 1):
            text.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 1000')
            for n, s, d in events:
                text.append(f"events {{ metadata_id: {ids[n]} offset_ps: "
                            f"{s * 1000} duration_ps: {d * 1000} }}")
            text.append("}")
        for n, i in ids.items():
            text.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}')
        text.append("}")
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace("\n".join(text)))


S = 1_000_000_000            # one second in ns


def _two_chip_trace():
    return _trace({
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 1 * S, 2 * S),            # 1..3
                        ("all-reduce.1", 3 * S, 1 * S),        # 3..4
                        ("fusion.2", 6 * S, 2 * S)],           # 6..8
            # other lines of the same chip must not count as operations
            "XLA Modules": [("jit_step", 1 * S, 7 * S)],
            "Async XLA Ops": [("copy-start.1", 0, 10 * S)]},
        "/device:TPU:1": {
            "XLA Ops": [("fusion.1", 1 * S, 4 * S),            # 1..5
                        ("all-reduce.1", 3 * S, 1 * S),        # inside it
                        ("fusion.2", 12 * S, 2 * S)]},         # outside
        # host events are not operations on a TPU, whatever they carry
        "/host:CPU": {"main": [("fit", 0, 20 * S)]},
    })


SPANS = [("fit", 0, 5 * S), ("fit", 5 * S, 10 * S),
         ("dispatch", 4 * S + S // 2, 5 * S + S // 2)]


def test_a_two_chip_trace_reduces_to_the_numbers_one_can_work_out_by_hand():
    s = tr.reduce_profile(_two_chip_trace(), (0, 10 * S), SPANS)
    assert s.chips == 2 and s.window_s == pytest.approx(10.0)
    assert s.busy_s_per_chip == pytest.approx([5.0, 4.0])
    assert s.busy_s == pytest.approx(4.5)
    assert s.idle_share == pytest.approx(0.55)
    # own time: on chip 1 the all-reduce ran inside fusion.1's four seconds
    assert s.op_seconds == pytest.approx(
        {"fusion.1": 2.5, "all-reduce.1": 1.0, "fusion.2": 1.0})
    assert sum(s.op_seconds.values()) == pytest.approx(s.busy_s)
    assert s.top_ops(2) == [["fusion.1", pytest.approx(2.5)],
                            ["all-reduce.1", pytest.approx(1.0)]]
    # chip 0 idles 0..1 (in fit), 4..6 (its middle in the dispatch), 8..10
    assert s.gap_seconds == pytest.approx({"fit": 3.0, "dispatch": 2.0})
    assert s.longest_gap_s == pytest.approx(2.0)


def test_the_window_clips_operations_and_gaps_without_a_span_are_named_so():
    s = tr.reduce_profile(_two_chip_trace(), (2 * S, 13 * S))
    assert s.window_s == pytest.approx(11.0)
    assert s.busy_s_per_chip == pytest.approx([4.0, 4.0])   # 2..4 6..8; 2..5 12..13
    assert s.gap_seconds == pytest.approx({"(no span)": 7.0})
    with pytest.raises(ValueError, match="empty"):
        tr.reduce_profile(_two_chip_trace(), (3 * S, 3 * S))


def test_a_trace_without_operations_of_the_platform_is_an_error():
    host_only = _trace({"/host:CPU": {"main": [("fit", 0, S)]}})
    with pytest.raises(ValueError, match="no tpu operation"):
        tr.reduce_profile(host_only, (0, S))
    # the CPU's operations are host events with an hlo_op stat: a TPU trace
    # read as a CPU's has none, and the platform is never guessed
    with pytest.raises(ValueError, match="no cpu operation"):
        tr.reduce_profile(_two_chip_trace(), (0, 10 * S), platform="cpu")


# ---------------------------------------------- HLO text, nesting, kinds

FUSION = ("%fusion.6 = bf16[2048,56,56,256]{3,0,2,1:T(8,128)(2,1)} fusion("
          "bf16[2048,56,56,256]{3,0,2,1:T(8,128)(2,1)} %fusion.4, f32[256]"
          "{0:T(256)S(1)} %copy-done.97), kind=kOutput, calls=%fused_computation.10")
WHILE = ("%while.264 = (s32[]{:T(128)}, s32[2,200,48,16]{1,3,2,0:T(8,128)S(1)}"
         ") while((s32[]{:T(128)}, s32[2,200,48,16]{1,3,2,0}) %tuple.863), "
         "condition=%wide.region_13.30.clone, body=%wide.region_11.29.sunk")
ALLREDUCE = ("%all-reduce.5 = s32[16,200,48,16]{3,2,1,0:T(8,128)} all-reduce("
             "s32[16,200,48,16]{3,2,1,0:T(8,128)} %fusion.9), channel_id=3, "
             "replica_groups={{0,1,2,3}}, to_apply=%region_3.7")


@pytest.mark.parametrize("text,name,kind", [
    (FUSION, "fusion.6", "fusion bf16[2048,56,56,256]"),
    (WHILE, "while.264", "while (tuple)"),
    (ALLREDUCE, "all-reduce.5", "all-reduce s32[16,200,48,16]"),
    ("dot_general.1", "dot_general.1", "dot_general.1"),   # the CPU backend
])
def test_an_events_hlo_text_gives_its_name_and_its_kind(text, name, kind):
    assert tr.op_name(text) == name and tr.op_kind(text) == kind


def test_own_time_is_duration_less_what_nests_inside():
    ops = [("while", 0, 100), ("a", 10, 30), ("b", 30, 60),
           ("inner_while", 60, 90), ("c", 65, 85), ("d", 120, 130)]
    assert sorted(tr.self_times(ops)) == [
        ("a", 20), ("b", 30), ("c", 20), ("d", 10), ("inner_while", 10),
        ("while", 20)]
    assert sum(t for _, t in tr.self_times(ops)) == 110      # the busy time


def test_a_while_keeps_what_its_body_leaves_and_kinds_add_up():
    profile = _trace({
        "/device:TPU:0": {"XLA Ops": [
            (WHILE, 0, 10 * S),
            (FUSION, 1 * S, 2 * S),
            (FUSION.replace("fusion.6", "fusion.8"), 3 * S, 1 * S),
            (ALLREDUCE, 5 * S, 2 * S)]}})
    s = tr.reduce_profile(profile, (0, 10 * S))
    # a program on the device is busy time; a wait inside it is the while's own
    assert s.busy_s == pytest.approx(10.0)
    assert s.op_seconds == pytest.approx({
        "fusion bf16[2048,56,56,256] (fusion.6 +1)": 3.0,
        "all-reduce s32[16,200,48,16] (all-reduce.5)": 2.0,
        "while (tuple) (while.264)": 5.0})
    assert sum(s.op_seconds.values()) == pytest.approx(s.busy_s)


# ------------------------------------------------ the trace from the chip

def test_the_recorded_chip_trace_reduces_to_its_pinned_numbers():
    """The first quarter second of the traced window of PR 23's first
    ``gbdt-train-1chip`` run on a v5e (5M x 200 rows), cut by
    ``benchmark/tools/trace_tool.py cut --seconds 0.25 --skip 0``: the fit
    begins, the host dispatches small programs, then ``lightgbm.multi_iter``
    starts its first iteration (sort, layout, routing)."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(
        os.path.join(FIXTURES, "gbdt-train-1chip.first-250ms.xplane.pb"))
    # the cut kept the host's events of that run (its host tracer was on):
    # the cut's extent, the harness's ``fit`` and JAX's dispatches, which
    # here stand for the spans a live run hands over
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in profile.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]
    (_, lo, hi), = [h for h in host if h[0] == "window"]
    s = tr.reduce_profile(profile, (lo, hi),
                          [h for h in host if h[0] != "window"])
    assert s.chips == 1
    assert s.window_s == pytest.approx(0.25, abs=1e-9)
    assert s.busy_s == pytest.approx(0.231938409, abs=1e-8)
    assert s.idle_share == pytest.approx(0.072246, abs=1e-5)
    assert s.longest_gap_s == pytest.approx(0.007643639, abs=1e-8)
    assert sum(s.op_seconds.values()) == pytest.approx(s.busy_s)
    top = s.top_ops(5)
    assert [n for n, _ in top] == [
        "fusion s32[5009408] (fusion.723 +2)",
        "fusion u8[5009408,200] (fusion.724)",
        "fusion s32[2] (fusion.720)",
        "fusion s32[5000000] (clamp_convert_fusion.4 +3)",
        "sort (tuple) (sort.122 +1)"]
    assert top[0][1] == pytest.approx(0.069561991, abs=1e-8)
    gaps = dict(s.top_gaps(10))
    assert gaps["fit"] == pytest.approx(0.009884021, abs=1e-8)
    assert gaps["PjitFunction(convert_element_type)"] == pytest.approx(
        0.004838081, abs=1e-8)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    # the while loops that hold the iteration keep next to nothing for
    # themselves: their time is their bodies'
    assert all(t < 1e-3 for n, t in s.op_seconds.items()
               if n.startswith("while "))


def test_the_harness_lays_its_own_window_and_spans_over_the_trace(tmp_path):
    """A live run gives the window and its spans in wall-clock time; the
    trace says when its own clock started."""
    from jax.profiler import ProfileData
    zero = 1_790_000_000 * S
    text = f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: {2 * S * 1000} duration_ps: {3 * S * 1000} }}
    events {{ metadata_id: 1 offset_ps: {7 * S * 1000} duration_ps: {1 * S * 1000} }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }} }}
planes {{ id: 2 name: "Task Environment"
  stats {{ metadata_id: 1 uint64_value: {zero} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "profile_start_time" }} }} }}'''
    profile = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    assert tr.profile_start_ns(profile) == zero
    path = str(tmp_path / "live.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    s = tr.reduce_file(
        path, window_wall_ns=(zero + 1 * S, zero + 9 * S),
        wall_spans=[("transform", zero + 0 * S, zero + 6 * S),
                    ("apply_batch", zero + 1 * S + S // 2, zero + 5 * S),
                    ("transform", zero + 6 * S, zero + 10 * S)])
    assert s.window_s == pytest.approx(8.0)
    assert s.busy_s == pytest.approx(4.0) and s.idle_share == pytest.approx(0.5)
    # idle 1..2 (inside apply_batch), 5..7 (middle at 6: a transform), 8..9
    assert s.gap_seconds == pytest.approx({"apply_batch": 1.0, "transform": 3.0})
    # a trace that does not say when it started cannot be laid over
    with pytest.raises(ValueError, match="when it started"):
        tr.profile_start_ns(_two_chip_trace())

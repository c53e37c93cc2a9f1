"""``trace_reduce.py``: interval arithmetic on a hand-made trace, and a
reduction of a trace recorded on the chip (``fixtures/*.xplane.pb``)."""
import glob
import os

import pytest
from jax.profiler import ProfileData

from benchmarks import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def plane(name, line, events, pid):
    """Text-proto of one plane; times in nanoseconds."""
    ids = {n: i + 1 for i, n in enumerate(sorted({n for _, _, n in events}))}
    quoted = lambda n: n.replace('"', '\\"')  # noqa: E731
    ev = "".join(f"events {{ metadata_id: {ids[n]} offset_ps: {a * 1000} "
                 f"duration_ps: {(b - a) * 1000} }} " for a, b, n in events)
    md = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{quoted(n)}" }} }} '
                 for n, i in ids.items())
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 name: "{line}" '
            f"timestamp_ns: 1000 {ev} }} {md} }} ")


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert tr.length([[0, 3], [5, 8]]) == 6
    assert tr.subtract([[0, 10], [20, 30]], [[2, 4], [8, 22], [29, 40]]) == \
        [[0, 2], [4, 8], [22, 29]]
    assert tr.subtract([[0, 10]], []) == [[0, 10]]


def test_busy_idle_and_exposed_collective():
    # names as the profiler gives them: whole HLO instructions. A psum's
    # opcode is all-reduce; fusion.2 only TAKES an all-reduce as operand
    start = "%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %x)"
    done = "%all-reduce-done.1 = f32[8]{0} all-reduce-done(f32[8]{0} %all-reduce-start.1)"
    psum = "%psum.3 = (f32[8]{0}, f32[4]{0}) all-reduce(f32[8]{0} %y, f32[4]{0} %z)"
    fus1 = "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p0), kind=kLoop"
    fus2 = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %all-reduce-done.1), kind=kLoop"
    chip0 = [(0, 100, fus1), (100, 150, start), (150, 300, fus2), (300, 380, done),
             (500, 600, fus1)]
    chip1 = [(0, 200, fus1), (150, 260, psum)]   # 50 ns hidden behind fusion.1
    host = [(370, 520, "wait_for_batch"), (0, 10, "dispatch")]
    text = (plane("/device:TPU:0", "XLA Ops", chip0, 1)
            + plane("/device:TPU:1", "XLA Ops", chip1, 2)
            + plane("/device:TPU:1 extra", "XLA Ops", [(0, 999, "ignored")], 3)
            + plane("/host:CPU", "main", host, 4))
    r = tr.reduce(ProfileData.from_text_proto(text))
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((480 + 260) / 2 * 1e-9)
    assert r["collective_s"] == pytest.approx((130 + 110) / 2 * 1e-9)
    assert r["exposed_collective_s"] == pytest.approx((130 + 60) / 2 * 1e-9)
    assert r["ops_s"]["fusion.1"] == pytest.approx((200 + 200) / 2 * 1e-9)
    assert r["device_ops"][0][0] == "fusion.1"
    assert r["idle_gaps"][0] == ["wait_for_batch", pytest.approx(120e-9)]
    assert tr.is_collective(psum) and not tr.is_collective(fus2)
    assert tr.op_name(psum) == "psum.3"


def test_an_idle_gap_is_named_by_the_innermost_host_event():
    """``bench_step`` wraps every step and overlaps every gap longest: the gap
    takes the name of the event nested deepest inside it that overlaps the gap,
    the longest such overlap first; a gap no host event overlaps is not traced."""
    fus = "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p0), kind=kLoop"
    chip = [(0, 100, fus), (400, 500, fus), (600, 700, fus), (900, 1000, fus), (2000, 2100, fus)]
    host = [(0, 1000, "bench_step"), (90, 450, "fetch_loss"), (120, 380, "device_get"),
            (480, 560, "dispatch"), (560, 650, "feed.get"), (700, 1000, "fetch_loss")]
    text = (plane("/device:TPU:0", "XLA Ops", chip, 1) + plane("/host:CPU", "main", host, 2))
    r = tr.reduce(ProfileData.from_text_proto(text))
    assert r["idle_gaps"] == [["host not traced", pytest.approx(1000e-9)],
                              ["device_get", pytest.approx(300e-9)],      # inside fetch_loss
                              ["fetch_loss", pytest.approx(200e-9)],      # nothing inside it
                              ["dispatch", pytest.approx(100e-9)]]        # 60 ns against 40
    assert tr.innermost(0, 10, [(0, 1000, "bench_step")]) == "bench_step"   # alone, it names it


def test_a_trace_without_a_device_reads_as_nothing():
    r = tr.reduce(ProfileData.from_text_proto(plane("/host:CPU", "main", [(0, 5, "x")], 1)))
    assert r["chips"] == 0 and r["busy_s"] == 0.0


def test_recorded_trace_from_the_chip():
    files = glob.glob(os.path.join(HERE, "fixtures", "*.xplane.pb"))
    assert files, "the recorded fixture is missing"
    r = tr.reduce(tr.load(files[0]))
    assert r["chips"] >= 1 and r["busy_s"] > 0
    total = sum(r["ops_s"].values())
    # one step of sc1b-train-8k, 487.98 ms by the trace's own step line
    assert r["chips"] == 1 and r["busy_s"] == pytest.approx(0.4855, abs=2e-4)
    assert r["collective_s"] == 0.0
    assert r["device_ops"][0][0] == "fusion.263"
    assert r["busy_s"] <= total * 1.0001          # a union never exceeds the sum
    assert len(r["device_ops"]) <= 10 and r["device_ops"][0][1] >= r["device_ops"][-1][1]

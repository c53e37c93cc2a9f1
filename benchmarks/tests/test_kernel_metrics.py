"""The kernels layer's readers (``flash_fwd_ms``, ``flash_bwd_ms``,
``xentropy_ms`` and the two roofline shares) on a hand-made trace whose
events are spelt as the chip spells them, on a trace of a program that names
nothing, and on one step of ``sc1b-train-8k`` recorded on the chip after the
kernels were named (``fixtures/named/``, stats stripped)."""
import glob
import os
import re

import pytest
from jax.profiler import ProfileData

from benchmarks import kernel_work, run, trace_reduce as tr
from benchmarks.reference import gpt_ref
from benchmarks.tests.test_trace_reduce import plane

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
PEAKS = run.load_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]
KERNEL_METRICS = ("flash_fwd_ms", "flash_bwd_ms", "xentropy_ms",
                  "flash_fwd_roofline_pct", "flash_bwd_roofline_pct")
TAIL = ', custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}'
# as the chip printed them in sc1b-train-8k (PR 24): the kernel's own name,
# because the program's spans stand around it in the scope path ...
FWD = ("%flash_fwd_packed.8 = (bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)}, "
       "f32[2,16,8192,8]{3,2,1,0:T(8,128)}) custom-call(bf16[2,8192,2304]{2,1,0:T(8,128)(2,1)} "
       "%bitcast.1159)" + TAIL)
DQ = ("%flash_bwd_packed_dq.8 = bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)} "
      "custom-call(bf16[2,8192,2304]{2,1,0:T(8,128)(2,1)} %bitcast.1225)" + TAIL)
XENT = ("%xentropy_stats.1 = (f32[16384,8]{1,0:T(8,128)S(1)}, f32[16384,8]{1,0:T(8,128)}) "
        "custom-call(bf16[16384,49152]{1,0:T(8,128)(2,1)} %bitcast.106)" + TAIL)
# ... and as the compiler spells them with no span around the call: the
# transforms' scopes folded into the name
FWD_BARE = ("%jvp_flash_fwd_packed_.2 = bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)} "
            "custom-call(bf16[2,8192,2304]{2,1,0} %bitcast.9)" + TAIL)
DKV_BARE = ("%transpose_jvp_flash_bwd_packed_dkv__.4 = (f32[2,8192,2048]{2,1,0:T(8,128)}, "
            "f32[2,8192,2048]{2,1,0:T(8,128)}) custom-call(bf16[2,8192,2304]{2,1,0} %bitcast.7)" + TAIL)
FUSION = ("%fusion.263 = (bf16[2048]{0:T(1024)(128)(2,1)}, f32[2,8192]{1,0:T(2,128)S(1)}) "
          "fusion(bf16[16384,2048]{1,0} %p), kind=kOutput")
# the parent's: every Mosaic call named after its transforms alone
UNNAMED = ("%jvp__.3 = bf16[2,8192,2048]{2,1,0} custom-call(bf16[2,8192,2304]{2,1,0} %b)" + TAIL,
           "%transpose_jvp___.17 = bf16[2,8192,2048]{2,1,0} custom-call(bf16[8]{0} %b)" + TAIL)


def dims(name):
    return gpt_ref.dims(run.load_json(os.path.join(HERE, "configs", name + ".json")))


def sc1b_run(trace, steps):
    """What ``run.execute`` hands the readers after a traced run of
    ``sc1b-train-8k``: 2 rows of 8,192 a step on one chip."""
    return {"trace": trace, "step_s": [0.488] * steps, "steps": 41, "tokens": 41 * 16384,
            "chips": 1, "seq": 8192, "dims": dims("starcoderbase-1b-train1"), "peaks": PEAKS}


def read(name, r):
    return run.load_reader(name).read(r)


def traced(events):
    text = (plane("/device:TPU:0", "XLA Ops", events, 1)
            + plane("/host:CPU", "python", [(0, 10, "bench_step")], 2))
    return tr.reduce(ProfileData.from_text_proto(text))


def test_readers_on_names_as_the_chip_spells_them():
    """Under the program's spans XLA names a Mosaic call after the kernel
    alone; with no span around it, after its transforms too. Two steps."""
    ms = 1_000_000
    events = [(0, 5 * ms, FWD), (5 * ms, 11 * ms, FWD_BARE), (11 * ms, 20 * ms, DQ),
              (20 * ms, 27 * ms, DKV_BARE), (27 * ms, 28 * ms, XENT), (28 * ms, 60 * ms, FUSION)]
    r = sc1b_run(traced(events), steps=2)
    assert read("flash_fwd_ms", r) == pytest.approx((5 + 6) / 2)
    assert read("flash_bwd_ms", r) == pytest.approx((9 + 7) / 2)
    assert read("xentropy_ms", r) == pytest.approx(1 / 2)
    # 8 layers x 4 x 2048 x 4096.5 x 16,384 tokens = 4.40 TFLOP forward: 22.3 ms at 197 TFLOP/s
    assert read("flash_fwd_roofline_pct", r) == pytest.approx(100 * 22.33 / 5.5, rel=1e-3)
    assert read("flash_bwd_roofline_pct", r) == pytest.approx(100 * 44.66 / 8.0, rel=1e-3)


def test_a_program_that_names_nothing_reads_as_nothing():
    """The parent's trace: ``jvp__.N`` and ``transpose_jvp___.N``."""
    events = [(0, 5, UNNAMED[0]), (5, 9, UNNAMED[1]), (9, 20, FUSION)]
    r = sc1b_run(traced(events), steps=1)
    assert [read(name, r) for name in KERNEL_METRICS] == [None] * 5
    no_trace = dict(r, trace=None)
    no_device = dict(r, trace=tr.reduce(ProfileData.from_text_proto(
        plane("/host:CPU", "python", [(0, 5, "x")], 1))))
    for other in (no_trace, no_device):
        assert [read(name, other) for name in KERNEL_METRICS] == [None] * 5


@pytest.mark.parametrize("name,rows,seq,ops_ms,bytes_ms", [
    # a layer forward: 4 x 2048 x 4096.5 x 16,384 = 550 GFLOP; q, o 2048 wide, k, v 128
    # wide, bf16, + 16 float32 a token = 143.7 MB
    ("starcoderbase-1b-train1", 2, 8192, 2.791, 0.1754),
    # 4 x 1024 x 512.5 x 8,192 = 17.2 GFLOP; 4 x 1024 wide bf16 + 16 float32 = 67.6 MB
    ("gpt2-medium", 8, 1024, 0.0873, 0.0826)])
def test_required_work_by_hand(name, rows, seq, ops_ms, bytes_ms):
    d = dims(name)
    r = {"dims": d, "seq": seq, "tokens": 10 * rows * seq * 4, "steps": 10, "chips": 4}
    ops, nbytes = kernel_work.flash_work(r)
    assert 1e3 * ops / d["n_layer"] / 197e12 == pytest.approx(ops_ms, rel=1e-3)
    assert 1e3 * nbytes / d["n_layer"] / 819e9 == pytest.approx(bytes_ms, rel=1e-3)
    back_ops, back_bytes = kernel_work.flash_work(r, backward=True)
    assert back_ops == 2 * ops
    assert back_bytes == pytest.approx(2 * nbytes - d["n_layer"] * rows * seq * 4 * d["n_head"])


def test_every_train_cell_reports_the_kernel_metrics():
    m = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for cell in m["workloads"]:
        assert set(KERNEL_METRICS) <= {p["name"] for p in run.metrics_of(m, "per_layer", cell)}
    listed = {p["name"]: p for p in m["per_layer"]}
    for name in KERNEL_METRICS:
        assert listed[name]["source"] == "device_trace" and "workloads" not in listed[name]
        assert (listed[name]["better"] == "lower") == name.endswith("_ms")


def test_recorded_named_trace_from_the_chip():
    """One step of ``sc1b-train-8k`` traced on the chip with the kernels
    named (PR 24's run, ``strip_trace.py``): the readers against the
    trace's own numbers, and no Mosaic call left with a transform's name."""
    files = glob.glob(os.path.join(HERE, "tests", "fixtures", "named", "*.xplane.pb"))
    assert len(files) == 1
    trace = tr.reduce(tr.load(files[0]))
    assert trace["chips"] == 1 and trace["busy_s"] == pytest.approx(0.48554, abs=1e-4)
    r = sc1b_run(trace, steps=1)
    fwd, bwd, xent = (read(n, r) for n in KERNEL_METRICS[:3])
    # eight layers: forward 5.40 ms, dq 6.38 ms, dkv 9.38 ms a layer; one CE kernel
    assert fwd == pytest.approx(43.214, abs=1e-3)
    assert bwd == pytest.approx(51.025 + 75.038, abs=2e-3)
    assert xent == pytest.approx(2.533, abs=1e-3)
    assert read("flash_fwd_roofline_pct", r) == pytest.approx(51.67, abs=0.01)
    assert read("flash_bwd_roofline_pct", r) == pytest.approx(35.42, abs=0.01)
    kernels = {n: s for n, s in trace["ops_s"].items()
               if re.match(r"(flash_|xentropy_|jvp_|transpose_jvp_)", n)}
    assert sorted({n.rsplit(".", 1)[0] for n in kernels}) == [
        "flash_bwd_packed_dkv", "flash_bwd_packed_dq", "flash_fwd_packed", "xentropy_stats"]
    assert len(kernels) == 25
    assert 1e3 * sum(kernels.values()) == pytest.approx(fwd + bwd + xent)
    # the breakdown the ledger prints names kernels now
    assert trace["device_ops"][3][0].startswith("flash_bwd_packed_dkv.")

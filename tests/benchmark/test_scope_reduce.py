"""``benchmark/scope_reduce.py``: device time by class of work and idle gaps
by program span, on a recorded slice of two scoped steps from the chip and on
small made-up traces whose answers can be worked out by hand."""

from __future__ import annotations

import json
import os
import sys

import pytest

import test_harness as th

TESTDATA = os.path.join(th.BENCH, "testdata")
SLICE = os.path.join(TESTDATA, "lm_scoped_slice.xplane.pb")


@pytest.fixture(scope="module")
def sr():
    if th.BENCH not in sys.path:  # scope_reduce imports trace_reduce beside it
        sys.path.insert(0, th.BENCH)
    return th._load(os.path.join(th.BENCH, "scope_reduce.py"), "bench_scope_reduce")


@pytest.fixture(scope="module")
def recorder(sr):
    return th._load(os.path.join(TESTDATA, "record_lm_scoped_slice.py"),
                    "bench_record_slice")


def test_recorded_slice_reduces_to_its_expected_json(sr):
    assert os.path.getsize(SLICE) <= 1_000_000
    with open(os.path.join(TESTDATA, "lm_scoped_slice.expected.json")) as f:
        expected = json.load(f)
    got = sr.reduce_file(SLICE)
    assert got == expected
    assert got == sr.reduce_file(SLICE)


def test_recorded_slice_classes_sum_to_the_busy_union(sr):
    got = sr.reduce_file(SLICE)
    assert got["steps"] == 2 and got["ops_outside_steps"] == 0
    classes = got["class_ms_per_step"]
    assert set(classes) == set(sr.CLASSES)
    assert all(classes[c] > 0 for c in sr.CLASSES)
    busy_ms_per_step = 1e3 * got["busy_s"] / got["steps"]
    assert sum(classes.values()) == pytest.approx(busy_ms_per_step, rel=0.01)
    assert classes["other"] < 0.05 * busy_ms_per_step
    for cls, modules in got["module_ms_per_step"].items():
        assert sum(modules.values()) == pytest.approx(classes[cls], rel=1e-9)
    # the program's spans name the idle time between the steps
    assert got["idle_named_by_program_share"] > 0.9
    assert set(got["idle_gaps_s"]) >= {
        "fedml:lm.loss_wait", "fedml:lm.input_put", "fedml:lm.dispatch"}
    named = sum(t for k, t in got["idle_gaps_s"].items()
                if k != "between_ops_under_10us")
    assert named == pytest.approx(got["idle_over_10us_s"], rel=1e-9)


@pytest.mark.parametrize("op_name,cls,module", [
    ("jit(train_step)/lm.optimizer/add:", "optimizer", None),
    ("jit(train_step)/jvp(lm.loss)/jit(log_softmax)/sub:", "head_ce", None),
    ("jit(train_step)/transpose(jvp(lm.loss))/jit(log_softmax)/reduce_sum:",
     "head_ce", None),
    ("jit(train_step)/transpose(jvp(TransformerLM))/head/dot_general:",
     "head_ce", None),
    ("jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/"
     "checkpoint/rematted_computation/block_1/LayerNorm_0/mul:",
     "recompute", "norm"),
    ("jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/"
     "checkpoint/block_1/MLPBlock_0/Dense_1/dot_general:", "backward", "mlp"),
    ("jit(train_step)/jvp(TransformerLM)/block_0/SelfAttention_0/qkv/"
     "dot_general:", "forward", "attention_proj"),
    ("jit(train_step)/jvp(TransformerLM)/block_0/SelfAttention_0/"
     "SelfAttention_0._local_attention/bhqk,bkhd->bqhd/dot_general:",
     "forward", "attention_core"),
    ("jit(train_step)/jvp(TransformerLM)/wte/jit(_take)/gather:",
     "forward", "rest"),
    ("jit(train_step)/jvp(TransformerLM)/ln_f/rsqrt:", "forward", "norm"),
    ("", "other", None),
])
def test_classes_are_tested_in_order(sr, op_name, cls, module):
    assert sr.classify(op_name) == cls
    if module:
        assert sr.module_of(op_name) == module


def test_share_gives_each_instant_to_the_interval_that_started_last(sr):
    # a while op over two children, then an op that outlasts its neighbour
    got, covered = sr.share([(0, 100, "while"), (10, 30, "a"), (40, 60, "b"),
                             (200, 260, "c"), (220, 240, "d")])
    assert got == {"while": 60, "a": 20, "b": 20, "c": 40, "d": 20}
    assert covered == 160
    # clipped to a gap: only what lies in it counts
    got, covered = sr.share([(0, 50, "step"), (5, 20, "put"), (20, 45, "run")],
                            lo=10, hi=30)
    assert got == {"put": 10, "run": 10} and covered == 20
    assert sr.share([]) == ({}, 0)


def _xspace(sr, recorder, device_events, host_events, tf_ops):
    """A two-plane trace from (start_ns, duration_ns, name) rows."""
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(
        recorder.plane_text(1, "/device:TPU:0", sr.OPS_LINE, device_events, tf_ops)
        + recorder.plane_text(2, "/host:CPU", "python3", host_events))


def test_made_up_trace_gives_the_hand_worked_answer(sr, recorder, tmp_path):
    us = 1000
    tf_ops = {
        "%fwd": "jit(train_step)/jvp(TransformerLM)/block_0/MLPBlock_0/Dense_0/dot_general:",
        "%bwd": "jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/block_0/SelfAttention_0/qkv/dot_general:",
        "%opt": "jit(train_step)/lm.optimizer/add:",
        "%copy": ""}
    device, host = [], []
    for k in range(2):  # two steps of 1000 us, the device idle for 100 between
        t = k * 1000 * us
        host += [(t, 990 * us, "fedml:lm.step"),
                 (t + 10 * us, 30 * us, "fedml:lm.input_put"),
                 (t + 40 * us, 40 * us, "fedml:lm.dispatch"),
                 (t + 80 * us, 900 * us, "fedml:lm.loss_wait")]
        device += [(t + 50 * us, 300 * us, "%fwd"), (t + 350 * us, 400 * us, "%bwd"),
                   (t + 750 * us, 150 * us, "%opt"), (t + 900 * us, 50 * us, "%copy")]
    host.append((0, 2000 * us, "bench:lm_window"))
    path = tmp_path / "made_up.xplane.pb"
    path.write_bytes(_xspace(sr, recorder, device, host, tf_ops))
    assert sr.event_op_names(str(path)) == {"/device:TPU:0": tf_ops}
    got = sr.reduce_file(str(path))
    assert got["steps"] == 2 and got["ops_per_step"] == 4
    assert got["busy_ms_per_step"] == pytest.approx(0.9)
    assert got["class_ms_per_step"] == pytest.approx({
        "optimizer": 0.15, "head_ce": 0, "recompute": 0, "backward": 0.4,
        "forward": 0.3, "other": 0.05})
    assert got["module_ms_per_step"]["forward"]["mlp"] == pytest.approx(0.3)
    assert got["module_ms_per_step"]["backward"]["attention_proj"] == pytest.approx(0.4)
    # the one gap, 950 -> 1050 us: 30 of loss_wait's tail, 10 outside every
    # program span (the benchmark's), 10 of lm.step alone, 30 of input_put,
    # 10 of dispatch before the device starts
    assert got["idle_over_10us_s"] == pytest.approx(100e-6)
    assert got["idle_gaps_s"] == pytest.approx({
        "fedml:lm.loss_wait": 30e-6, "fedml:lm.input_put": 30e-6,
        "fedml:lm.dispatch": 10e-6, "fedml:lm.step": 20e-6,
        "bench:lm_window": 10e-6})
    assert got["idle_named_by_program_share"] == pytest.approx(0.9)
    # steps bounded by another span, as for a program without fedml: spans
    assert sr.reduce_file(str(path), "bench:lm_window")["steps"] == 1


def test_no_device_plane_gives_nothing(sr, tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "host_only.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/host:CPU" }'))
    assert sr.reduce_file(str(path)) is None
    assert sr.find_xplane(str(tmp_path / "nowhere")) is None

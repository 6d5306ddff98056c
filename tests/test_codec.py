"""Compressed update plane (comm/codec.py): spec grammar, per-stage numpy
oracles, stochastic-rounding determinism, numpy<->JAX bit parity, 4-backend
frame parity, and the end-to-end accuracy-vs-bytes acceptance drill.

The oracles pin the arithmetic contracts the codec advertises:

- q8 error < amax/32 and q4 error < amax/2 per 256-chunk (pow2 scales);
- delta as terminal stage is bit-exact for float32 (f64 carrier);
- top-k with error feedback converges on a quadratic where plain top-k
  stalls at its truncation bias;
- the same (seed, round, client) always yields the same bytes, and any
  change to the tuple changes the rounding stream.
"""

import logging
import math
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.comm import (
    LoopbackCommManager,
    LoopbackHub,
    InMemoryBlobStore,
    InProcessBroker,
    Message,
    MqttS3CommManager,
)
from fedml_tpu.comm import codec as codec_mod
from fedml_tpu.comm.codec import (
    UpdateCodec,
    build_stacked_roundtrip,
    decode_tree,
    dequantize,
    downlink_spec,
    encode_tree,
    frame_nbytes,
    is_codec_frame,
    pack_int4,
    parse_codec_spec,
    resolve_codec_spec,
    resolve_downlink_spec,
    spec_wire_nbytes,
    stochastic_quantize,
    tree_nbytes,
    unpack_int4,
)
from fedml_tpu.comm.message import compress_tree, decompress_tree
from fedml_tpu.core import telemetry


# ------------------------------------------------------------ spec grammar

def test_parse_spec_full_pipeline():
    cs = parse_codec_spec("delta|topk:0.01|q8")
    assert cs.delta and cs.topk == 0.01 and cs.bits == 8 and cs.bound == 127
    assert parse_codec_spec("q4").bound == 7
    assert parse_codec_spec("delta").topk is None
    assert parse_codec_spec("topk:1.0|q4").topk == 1.0


@pytest.mark.parametrize("bad", [
    "", "zstd", "q8|q4", "topk:0", "topk:1.5", "topk:x", "topk:",
    "q8|delta", "topk:0.1|delta", "q8|topk:0.1", "delta|delta",
])
def test_parse_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_codec_spec(bad)


def test_resolve_spec_precedence():
    # explicit comm_codec beats the deprecated comm_quantize shim
    assert resolve_codec_spec(
        SimpleNamespace(comm_codec="q4", comm_quantize=True)) == "q4"
    # "none"/"off" disable even with the shim set
    assert resolve_codec_spec(
        SimpleNamespace(comm_codec="none", comm_quantize=True)) is None
    # unset -> codec off entirely
    assert resolve_codec_spec(SimpleNamespace()) is None
    # "auto" resolves per wire backend
    auto = SimpleNamespace(comm_codec="auto")
    assert resolve_codec_spec(auto, "MQTT_S3") == "delta|topk:0.01|q8"
    assert resolve_codec_spec(auto, "GRPC") == "q8"
    assert resolve_codec_spec(auto, "LOOPBACK") is None
    # invalid specs are rejected at config time
    with pytest.raises(ValueError):
        resolve_codec_spec(SimpleNamespace(comm_codec="lz77"))


def test_comm_quantize_shim_warns_once(caplog):
    codec_mod._quantize_warned = False
    args = SimpleNamespace(comm_quantize=True)
    with caplog.at_level(logging.WARNING):
        assert resolve_codec_spec(args) == "q8"
        assert resolve_codec_spec(args) == "q8"
    warned = [r for r in caplog.records
              if "comm_quantize is deprecated" in r.getMessage()]
    assert len(warned) == 1


def test_downlink_projection_is_stateless():
    assert downlink_spec("delta|topk:0.01|q8") == "q8"
    assert downlink_spec("delta|topk:0.01|q4") == "q4"
    assert downlink_spec("delta") is None
    assert downlink_spec(None) is None
    # explicit override: quant-only accepted, stateful stages rejected
    assert resolve_downlink_spec(
        SimpleNamespace(comm_codec_downlink="q4"), "delta|topk:0.01|q8") == "q4"
    assert resolve_downlink_spec(
        SimpleNamespace(comm_codec_downlink="none"), "q8") is None
    assert resolve_downlink_spec(
        SimpleNamespace(comm_codec_downlink="auto"), "delta|topk:0.1|q8") == "q8"
    with pytest.raises(ValueError):
        resolve_downlink_spec(
            SimpleNamespace(comm_codec_downlink="topk:0.1|q8"), "q8")


# --------------------------------------------------- quantization oracles

def test_quant_error_bound_per_chunk():
    rng = np.random.default_rng(0)
    vals = (rng.standard_normal(1024) * 3.0).astype(np.float32)
    for bits, denom in ((8, 32.0), (4, 2.0)):
        q, s, dec = stochastic_quantize(vals, bits, 1, 2, 3)
        assert q.dtype == np.int8 and abs(int(q.max())) <= {8: 127, 4: 7}[bits]
        # pow2 scale: s = 2^(ea-eb) <= 2*amax/2^eb, and one stochastic
        # rounding step contributes < 1 level of error
        err = np.abs(dec - vals).reshape(4, 256)
        amax = np.abs(vals.reshape(4, 256)).max(axis=1)
        assert (err.max(axis=1) <= amax / denom).all()
        np.testing.assert_array_equal(dec, dequantize(q, s, vals.size))


def test_quant_unbiased_on_flat_block():
    # stochastic rounding of a constant mid-level value averages back to it
    v = np.full(4096, 0.3, np.float32)
    _, _, dec = stochastic_quantize(v, 8, 9, 0, 0)
    assert abs(float(dec.mean()) - 0.3) < 1e-3
    assert set(np.round(np.unique(dec / dec.min())).astype(int)) <= {1, 2}


def test_stochastic_rounding_deterministic_per_key():
    rng = np.random.default_rng(1)
    vals = rng.standard_normal(512).astype(np.float32)
    a = stochastic_quantize(vals, 8, 7, 3, 11)
    b = stochastic_quantize(vals, 8, 7, 3, 11)
    np.testing.assert_array_equal(a[0], b[0])  # same key -> same bytes
    for other in ((8, 3, 11), (7, 4, 11), (7, 3, 12)):  # seed/round/client
        c = stochastic_quantize(vals, 8, *other)
        assert (a[0] != c[0]).any()
    d = stochastic_quantize(vals, 8, 7, 3, 11, leaf_hash=99)
    assert (a[0] != d[0]).any()


def test_int4_pack_roundtrip_odd_length():
    rng = np.random.default_rng(2)
    q = rng.integers(-7, 8, size=33).astype(np.int8)
    packed = pack_int4(q)
    assert packed.dtype == np.uint8 and packed.size == 17
    np.testing.assert_array_equal(unpack_int4(packed, 33), q)


def test_delta_terminal_roundtrip_exact():
    rng = np.random.default_rng(3)
    base = {"w": rng.standard_normal(128).astype(np.float32),
            "b": rng.standard_normal(100).astype(np.float32)}
    tree = {"w": base["w"] + np.float32(1e-3) * rng.standard_normal(128).astype(np.float32),
            "b": base["b"] * np.float32(0.5)}
    frame = encode_tree(tree, "delta", base=base)
    assert is_codec_frame(frame)
    out = decode_tree(frame, base=base)
    # f64 carrier makes decode(encode(x)) bit-exact for float32 inputs
    np.testing.assert_array_equal(out["w"], tree["w"])
    np.testing.assert_array_equal(out["b"], tree["b"])
    assert out["w"].dtype == np.float32
    with pytest.raises(ValueError):
        decode_tree(frame)  # delta frames need the base


def test_dtype_restored_through_both_codecs():
    import ml_dtypes

    tree = {"w64": np.linspace(-1.0, 1.0, 256).astype(np.float64),
            "w32": np.linspace(-2.0, 2.0, 256).astype(np.float32),
            "bf": np.full((128,), 1.5, ml_dtypes.bfloat16),
            "steps": np.arange(10, dtype=np.int32)}
    # legacy int8 frame (the pre-codec wire format): dtype token rides along
    legacy = decompress_tree(compress_tree({k: tree[k] for k in ("w64", "w32", "steps")}))
    assert legacy["w64"].dtype == np.float64
    assert legacy["w32"].dtype == np.float32
    np.testing.assert_array_equal(legacy["steps"], tree["steps"])
    np.testing.assert_allclose(legacy["w64"], tree["w64"], atol=1.0 / 32)
    # pipeline frame
    out = decode_tree(encode_tree(tree, "q8", seed=5))
    assert out["w64"].dtype == np.float64
    assert out["bf"].dtype == np.dtype(ml_dtypes.bfloat16)
    assert out["steps"].dtype == np.int32
    np.testing.assert_allclose(out["w32"], tree["w32"], atol=2.0 / 32)


def test_topk_ef_converges_where_plain_topk_stalls():
    """Minimize 0.5*||x - t||^2 with compressed gradients: error feedback
    must drive the iterate into the target; the same spec without residual
    carry is stuck with its truncation bias."""
    rng = np.random.default_rng(4)
    t = rng.standard_normal(512).astype(np.float32)

    def descend(residuals):
        codec = UpdateCodec("topk:0.05|q8")
        x = np.zeros_like(t)
        # lr must respect the EF delay (~1/rho rounds between visits to a
        # coordinate): lr * delay < 2 or the replayed residual overshoots
        for r in range(200):
            g = {"g": x - t}
            ghat = codec.decode(codec.encode(
                g, seed=0, round_idx=r, client_id=0, residuals=residuals))["g"]
            x = x - np.float32(0.05) * ghat
        return float(np.linalg.norm(x - t) / np.linalg.norm(t))

    err_ef = descend({})
    err_plain = descend(None)
    assert err_ef < 1e-3
    assert err_plain > 0.1


def test_wire_nbytes_estimate_matches_frames():
    rng = np.random.default_rng(5)
    tree = {"layer": {"w": rng.standard_normal(300).astype(np.float32)},
            "bias": rng.standard_normal(10).astype(np.float32)}
    for spec in ("q8", "q4", "topk:0.1|q8", "delta|topk:0.1", "delta"):
        frame = encode_tree(tree, spec, seed=1)
        raw, coded = spec_wire_nbytes(spec, tree)
        assert raw == tree_nbytes(tree)
        assert coded == frame_nbytes(frame), spec
    raw, coded = spec_wire_nbytes("delta|topk:0.01|q8", tree)
    assert coded < raw / 10  # the acceptance-spec frame is >10x smaller


@pytest.mark.parametrize("weaker, stronger", [
    ("q8", "delta|topk:0.05|q8"),
    ("delta|topk:0.05|q8", "delta|topk:0.01|q8"),
])
def test_wire_bytes_drop_along_stronger_specs(weaker, stronger):
    """A count, on the estimate the test above holds equal to the frames:
    the documented ladder of specs (docs/comm_codecs.md) is ordered, each a
    strictly stronger compressor of the same tree than the one before."""
    rng = np.random.default_rng(5)
    tree = {"layer": {"w": rng.standard_normal(4000).astype(np.float32)},
            "bias": rng.standard_normal(10).astype(np.float32)}
    raw_w, coded_w = spec_wire_nbytes(weaker, tree)
    raw_s, coded_s = spec_wire_nbytes(stronger, tree)
    assert raw_w == raw_s == tree_nbytes(tree)
    assert coded_s < coded_w < raw_w


# ------------------------------------------------- numpy <-> JAX bit parity

def test_stacked_roundtrip_bit_parity_with_wire_codec():
    """The simulator's batched JAX codec and the numpy wire codec must agree
    BIT-exactly per client, including residual carry across rounds."""
    import jax.numpy as jnp

    rng = np.random.default_rng(6)
    C, cids, seed = 3, np.array([5, 9, 2], np.uint32), 13
    w = rng.standard_normal((C, 300)).astype(np.float32)
    b = rng.standard_normal((C, 10)).astype(np.float32)
    for spec in ("q8", "q4", "topk:0.1|q8", "delta|topk:0.05|q8"):
        cs = parse_codec_spec(spec)
        rt = build_stacked_roundtrip(spec, seed)
        codec = UpdateCodec(spec)
        res_np = [{} for _ in range(C)]
        res_jax = ({"layer": {"w": jnp.zeros((C, 300), jnp.float32)},
                    "bias": jnp.zeros((C, 10), jnp.float32)}
                   if cs.topk is not None else ())
        for rnd in range(2):
            upd = {"layer": {"w": jnp.asarray(w * np.float32(1 + rnd))},
                   "bias": jnp.asarray(b)}
            dec_jax, res_jax = rt(upd, res_jax,
                                  jnp.asarray(cids), jnp.uint32(rnd))
            for c in range(C):
                tree_c = {"layer": {"w": w[c] * np.float32(1 + rnd)},
                          "bias": b[c]}
                dec_np = codec.decode(codec.encode(
                    tree_c, seed=seed, round_idx=rnd, client_id=int(cids[c]),
                    residuals=res_np[c] if cs.topk is not None else None))
                np.testing.assert_array_equal(
                    np.asarray(dec_jax["layer"]["w"])[c],
                    dec_np["layer"]["w"], err_msg=f"{spec} round {rnd}")
                np.testing.assert_array_equal(
                    np.asarray(dec_jax["bias"])[c], dec_np["bias"])
                if cs.topk is not None:
                    np.testing.assert_array_equal(
                        np.asarray(res_jax["layer"]["w"])[c],
                        res_np[c]["layer/w"], err_msg=f"{spec} round {rnd}")
        if cs.topk is None:
            assert res_jax == ()  # untouched when no error feedback


# ------------------------------------------------------------- wire parity

def _sample_frame():
    rng = np.random.default_rng(7)
    tree = {"dense": {"kernel": rng.standard_normal((20, 15)).astype(np.float32)},
            "bias": rng.standard_normal(10).astype(np.float32)}
    frame = encode_tree(tree, "delta|topk:0.1|q8", seed=3, round_idx=1,
                        client_id=2)
    return frame, tree


def _assert_frame_equal(got, frame):
    assert is_codec_frame(got)
    assert got["spec"] == frame["spec"]
    assert set(got["leaves"]) == set(frame["leaves"])
    for path, rec in frame["leaves"].items():
        grec = got["leaves"][path]
        for key in ("q", "s", "idx", "v", "raw"):
            assert (key in rec) == (key in grec)
            if key in rec:
                a, b = np.asarray(rec[key]), np.asarray(grec[key])
                assert a.dtype == b.dtype, (path, key)
                np.testing.assert_array_equal(a, b, err_msg=f"{path}/{key}")
    # and the received frame decodes to the same tree
    a, b = decode_tree(frame), decode_tree(got)
    np.testing.assert_array_equal(a["dense"]["kernel"], b["dense"]["kernel"])


def _roundtrip_via(m_send, m_recv, frame):
    received = []

    class _Obs:
        def receive_message(self, t, m):
            received.append(m.get(Message.MSG_ARG_KEY_MODEL_PARAMS))

    msg = Message(3, m_send.rank, m_recv.rank)
    msg.add_params(Message.MSG_ARG_KEY_MODEL_PARAMS, frame)
    m_send.send_message(msg)
    m_recv.add_observer(_Obs())
    t = threading.Thread(target=m_recv.handle_receive_message, daemon=True)
    t.start()
    deadline = time.time() + 10
    while not received and time.time() < deadline:
        time.sleep(0.01)
    m_send.stop_receive_message()
    m_recv.stop_receive_message()
    t.join(timeout=5)
    assert received, "frame never arrived"
    return received[0]


def test_codec_frame_parity_loopback():
    frame, _ = _sample_frame()
    hub = LoopbackHub()
    m0 = LoopbackCommManager(0, 2, hub)
    m1 = LoopbackCommManager(1, 2, hub)
    _assert_frame_equal(_roundtrip_via(m0, m1, frame), frame)


def test_codec_frame_parity_grpc():
    pytest.importorskip("grpc")
    from fedml_tpu.comm.grpc_backend import GRPCCommManager

    frame, _ = _sample_frame()
    m0 = GRPCCommManager(rank=0, size=2, base_port=21890)
    m1 = GRPCCommManager(rank=1, size=2, base_port=21890)
    try:
        _assert_frame_equal(_roundtrip_via(m0, m1, frame), frame)
    finally:
        m0.stop_receive_message()
        m1.stop_receive_message()


def test_codec_frame_parity_mqtt_s3():
    frame, _ = _sample_frame()
    broker = InProcessBroker()
    store = InMemoryBlobStore()
    m0 = MqttS3CommManager(broker, store, rank=0, size=2, run_id="codec")
    m1 = MqttS3CommManager(broker, store, rank=1, size=2, run_id="codec")
    _assert_frame_equal(_roundtrip_via(m0, m1, frame), frame)


def test_codec_frame_parity_trpc():
    from fedml_tpu.comm.trpc_backend import TRPCCommManager

    frame, _ = _sample_frame()
    m0 = TRPCCommManager(rank=0, size=2, base_port=21990)
    m1 = TRPCCommManager(rank=1, size=2, base_port=21990)
    try:
        _assert_frame_equal(_roundtrip_via(m0, m1, frame), frame)
    finally:
        m0.stop_receive_message()
        m1.stop_receive_message()


def test_unset_codec_plain_wire_roundtrip():
    """With no codec configured the wire carries the raw tree: same bytes as
    a build without comm/codec.py (nothing marks, wraps, or re-encodes it)."""
    rng = np.random.default_rng(8)
    tree = {"w": rng.standard_normal(200).astype(np.float32),
            "b64": rng.standard_normal(70),
            "n": np.int64(3)}
    msg = Message(3, 1, 0)
    msg.add_params(Message.MSG_ARG_KEY_MODEL_PARAMS, tree)
    data = msg.to_bytes()
    got = Message.from_bytes(data).get(Message.MSG_ARG_KEY_MODEL_PARAMS)
    assert not is_codec_frame(got)
    for k in ("w", "b64"):
        assert got[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(got[k], tree[k])
    # byte-stability: packing the same message twice is deterministic
    msg2 = Message(3, 1, 0)
    msg2.add_params(Message.MSG_ARG_KEY_MODEL_PARAMS, tree)
    assert msg2.to_bytes() == data


# ----------------------------------------------- end-to-end acceptance gate

@pytest.fixture()
def _fresh_telemetry():
    telemetry.configure(enabled=True, reset=True)
    yield
    telemetry.configure(enabled=True, reset=True)


def test_cross_silo_codec_accuracy_within_2pct_at_10x(_fresh_telemetry):
    """ISSUE acceptance: the chaos-drill topology (fault-free here) under
    ``delta|topk:0.01|q8`` must lose at most 2%% of the uncompressed run's
    final eval accuracy while moving >=10x fewer uplink bytes. The band is
    one-sided: on 200 held-out samples the compressed run can score HIGHER
    (it does on this seed), and that is no fault of the codec."""
    from fedml_tpu.cross_silo.chaos import run_chaos_drill

    common = dict(comm_round=25, fault_drop_rate=0.0, fault_seed=0,
                  frequency_of_the_test=25)

    def final_acc(history):
        for rec in reversed(history):
            if "test_acc" in rec:
                return float(rec["test_acc"])
        raise AssertionError("no eval in drill history")

    clean = run_chaos_drill(**common)
    assert clean.ok
    coded = run_chaos_drill(comm_codec="delta|topk:0.01|q8", **common)
    assert coded.ok
    assert final_acc(clean.history) - final_acc(coded.history) <= 0.02
    assert coded.codec_ratio("uplink") >= 10.0
    assert coded.codec_bytes_wire["uplink"] > 0


def test_chaos_drill_absorbs_faults_on_compressed_frames(_fresh_telemetry):
    """chaos-drill --codec: drop/duplicate faults on codec frames are
    absorbed by the resilience plane and the codec counters populate."""
    from fedml_tpu.cross_silo.chaos import run_chaos_drill

    res = run_chaos_drill(comm_codec="delta|topk:0.05|q8",
                          fault_duplicate_rate=0.1)
    assert res.ok, res.summary()
    assert sum(res.faults_injected.values()) > 0
    assert res.codec_bytes_wire.get("uplink", 0) > 0
    assert res.codec_bytes_wire.get("downlink", 0) > 0  # q8 broadcast leg
    assert res.codec_ratio("uplink") > res.codec_ratio("downlink") >= 2.0
    assert "codec uplink" in res.summary()


def test_chaos_drill_byzantine_on_compressed_frames(_fresh_telemetry):
    """decompress-then-corrupt: a NaN byzantine silo corrupts the DECODED
    update, so the sanitizer quarantines it exactly as on raw frames while
    every honest update still travels compressed."""
    import numpy as _np

    from fedml_tpu.cross_silo.chaos import run_chaos_drill

    res = run_chaos_drill(
        comm_codec="delta|topk:0.05|q8",
        fault_byzantine_kind="nan", fault_byzantine_ranks=[2],
        sanitize_updates=True, fault_drop_rate=0.0,
        local_test_on_all_clients=True, comm_round=3,
        client_num_in_total=4, client_num_per_round=4)
    assert res.ok, res.summary()
    assert res.quarantined >= 3, res.summary()
    assert res.codec_bytes_wire.get("uplink", 0) > 0
    for h in res.history:
        assert h["quarantined"] == [2], h
        assert _np.isfinite(h["local_train_loss"]), h


def test_simulator_codec_off_is_bit_identical(_fresh_telemetry):
    """comm_codec unset and comm_codec="none" run the exact same round step
    as a pre-codec build; an explicit spec adds a codec phase to telemetry."""
    from fedml_tpu.simulation import build_simulator

    def run(**kw):
        base = dict(dataset="mnist", model="lr", debug_small_data=True,
                    client_num_in_total=3, client_num_per_round=3,
                    comm_round=2, learning_rate=0.1, epochs=1, batch_size=8,
                    frequency_of_the_test=1, random_seed=0, prefetch=False)
        base.update(kw)
        sim, apply_fn = build_simulator(fedml_tpu.init(config=base))
        return sim.run(apply_fn, log_fn=None)

    h_unset = run()
    h_none = run(comm_codec="none")
    accs = [r["test_acc"] for r in h_unset if "test_acc" in r]
    assert accs == [r["test_acc"] for r in h_none if "test_acc" in r]
    h_codec = run(comm_codec="q8")
    codec_phase = sum(r.get("phases", {}).get("codec", 0.0)
                     for r in h_codec if "phases" in r)
    assert codec_phase > 0.0
    counters = telemetry.get_registry().snapshot()["counters"]
    key = "fedml_codec_bytes_out{direction=encode,plane=uplink}"
    assert counters.get(key, 0.0) > 0.0

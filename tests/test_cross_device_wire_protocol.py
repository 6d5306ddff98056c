"""Cross-device protocol conformance: a stand-in "phone" that speaks ONLY
the public wire format — raw-socket MQTT 3.1.1 + the documented msgpack
message encoding — against the real cross-device server over the real-wire
broker (round-4 review #8; reference test/android_protocol_test/test_protocol.py
keeps the same kind of Python stand-in for its Android client).

The stand-in deliberately imports NOTHING from fedml_tpu.comm or
fedml_tpu.cross_silo: its MQTT framing and its ndarray codec are
re-implemented here from the protocol contract (MQTT 3.1.4 packets;
Message = msgpack map with msg_type/sender/receiver params, ndarrays as
ExtType 42 = msgpack((dtype, shape)) header + raw bytes; topics
fedml_{run}_0_{cid} down / fedml_{run}_{cid} up; >8 KB model payloads
offloaded to the blob store, key under "model_params" + "model_params_url").
Any server-side drift from that contract fails this test.
"""

import os
import socket
import struct
import threading
import time

import msgpack
import numpy as np
import pytest

# --- independent ndarray codec (protocol contract, NOT an import) ---------

_EXT = 42


def _nd_default(obj):
    arr = np.ascontiguousarray(np.asarray(obj))
    header = msgpack.packb((arr.dtype.str, list(arr.shape)))
    return msgpack.ExtType(_EXT, header + arr.tobytes())


def _nd_ext_hook(code, data):
    if code != _EXT:
        return msgpack.ExtType(code, data)
    up = msgpack.Unpacker()
    up.feed(data)
    dtype_str, shape = up.unpack()
    return np.frombuffer(data, dtype=np.dtype(dtype_str),
                         offset=up.tell()).reshape(shape).copy()


def wire_pack(obj) -> bytes:
    return msgpack.packb(obj, default=_nd_default, strict_types=False)


def wire_unpack(data: bytes):
    return msgpack.unpackb(data, ext_hook=_nd_ext_hook, strict_map_key=False)


# --- independent minimal MQTT 3.1.1 client --------------------------------

def _varlen(n: int) -> bytes:
    out = bytearray()
    while True:
        d, n = n % 128, n // 128
        out.append(d | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _mqtt_str(s: str) -> bytes:
    b = s.encode()
    return struct.pack(">H", len(b)) + b


class StandInPhone:
    """Raw-socket MQTT client: CONNECT, SUBSCRIBE(qos0), PUBLISH(qos0),
    and a blocking packet reader. QoS0 subscription means the broker
    delivers every message at qos0 (min rule) — no acking needed."""

    def __init__(self, host: str, port: int, client_id: str):
        self.sock = socket.create_connection((host, port), timeout=30)
        self.sock.settimeout(60)
        var = (_mqtt_str("MQTT") + b"\x04" + b"\x02"  # level 4, clean session
               + struct.pack(">H", 60) + _mqtt_str(client_id))
        self._send(0x10, var)
        ptype, body = self._read_packet()
        assert ptype == 0x20 and body[1] == 0, f"CONNACK refused: {body!r}"

    def _send(self, ptype_flags: int, var: bytes) -> None:
        self.sock.sendall(bytes([ptype_flags]) + _varlen(len(var)) + var)

    def _read_exact(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("broker closed")
            buf += chunk
        return buf

    def _read_packet(self):
        h = self._read_exact(1)[0]
        mult, length = 1, 0
        while True:
            d = self._read_exact(1)[0]
            length += (d & 0x7F) * mult
            if not d & 0x80:
                break
            mult *= 128
        return h & 0xF0, self._read_exact(length) if length else b""

    def subscribe(self, topic: str, pid: int = 1) -> None:
        var = struct.pack(">H", pid) + _mqtt_str(topic) + b"\x00"  # req qos0
        self._send(0x82, var)  # SUBSCRIBE has reserved flags 0b0010
        ptype, _ = self._read_packet()
        assert ptype == 0x90, "expected SUBACK"

    def publish(self, topic: str, payload: bytes) -> None:
        self._send(0x30, _mqtt_str(topic) + payload)  # qos0

    def read_publish(self):
        """Block until the next inbound PUBLISH; returns (topic, payload)."""
        while True:
            ptype, body = self._read_packet()
            if ptype != 0x30:
                continue  # ignore acks/pings
            tlen = struct.unpack(">H", body[:2])[0]
            topic = body[2:2 + tlen].decode()
            return topic, body[2 + tlen:]

    def close(self) -> None:
        try:
            self._send(0xE0, b"")  # DISCONNECT
        finally:
            self.sock.close()


def _delta_like(tree, delta):
    """The uplink protocol ships DELTAS (local - global), not full params
    (cross_silo/aggregator.py:108: new global = params + weighted-mean of
    deltas). A constant-0.01 delta = "training moved every weight by 0.01"."""
    if isinstance(tree, dict):
        return {k: _delta_like(v, delta) for k, v in tree.items()}
    if isinstance(tree, np.ndarray) and np.issubdtype(tree.dtype, np.floating):
        return np.full_like(tree, np.float32(delta))
    return np.zeros_like(tree)


def _fetch_params(msg: dict, store_dir: str):
    """Inline params or store-offloaded key+URL (the >8 KB path)."""
    mp = msg["model_params"]
    if isinstance(mp, (bytes, str)) and "model_params_url" in msg:
        key = mp if isinstance(mp, str) else mp.decode()
        with open(os.path.join(store_dir, key.replace("/", "_")), "rb") as f:
            return wire_unpack(f.read()), True
    return mp, False


def test_cross_device_round_with_wire_standin(tmp_path):
    """A full multi-round FL session driven end-to-end by the stand-in:
    CHECK->IDLE->INIT->upload->SYNC->upload->FINISH, all over real TCP."""
    import jax

    import fedml_tpu
    from fedml_tpu import data as data_mod, models as models_mod
    from fedml_tpu.comm.mqtt_wire import MqttBroker, MqttWireBroker
    from fedml_tpu.comm.store import FileSystemBlobStore
    from fedml_tpu.cross_device import ServerMNN

    store_dir = str(tmp_path / "store")
    args = fedml_tpu.init(config=dict(
        dataset="mnist", model="lr", debug_small_data=True,
        client_num_in_total=1, client_num_per_round=1, comm_round=2,
        learning_rate=0.1, batch_size=8, frequency_of_the_test=1,
        random_seed=0, global_model_file_path=str(tmp_path / "global.blob"),
    ))
    fed_data, output_dim = data_mod.load(args)
    model = models_mod.create(args, output_dim)
    sample = models_mod.sample_input_for(args, fed_data)
    variables = models_mod.init_params(model, jax.random.PRNGKey(0), sample)

    def apply_fn(v, x, train=False, rngs=None):
        return model.apply(v, x, train=train)

    broker = MqttBroker()  # real TCP broker on a random port
    server = ServerMNN(
        args, fed_data, variables, apply_fn=apply_fn, backend="MQTT_S3",
        broker=MqttWireBroker("127.0.0.1", broker.port,
                              client_id="server-rank0"),
        store=FileSystemBlobStore(root=store_dir),
    )

    # the stand-in subscribes BEFORE the server kicks the handshake so the
    # CHECK_CLIENT_STATUS broadcast is not lost (no retained messages)
    phone = StandInPhone("127.0.0.1", broker.port, "android-standin-1")
    phone.subscribe("fedml_0_0_1")  # downlink: {prefix}{run}_0_{cid}

    history = []
    server_err = []

    def run_server():
        try:
            history.extend(server.run() or [])
        except Exception as e:  # pragma: no cover
            server_err.append(e)

    t = threading.Thread(target=run_server, daemon=True)
    t.start()

    uplink = "fedml_0_1"
    saw = {"check": 0, "init": 0, "sync": 0, "finish": 0, "offloaded": 0}
    deadline = time.time() + 120
    phone.sock.settimeout(5)  # poll: surface a dead server between reads
    try:
        while time.time() < deadline:
            assert not server_err, server_err
            try:
                topic, payload = phone.read_publish()
            except socket.timeout:
                continue
            assert topic == "fedml_0_0_1"
            msg = wire_unpack(payload)
            mtype = msg["msg_type"]
            if mtype == 6:  # S2C_CHECK_CLIENT_STATUS -> announce IDLE
                saw["check"] += 1
                phone.publish(uplink, wire_pack({
                    "msg_type": 5, "sender": 1, "receiver": 0,
                    "client_status": "IDLE", "client_os": "Android",
                }))
            elif mtype in (1, 2):  # INIT_CONFIG / SYNC_MODEL
                saw["init" if mtype == 1 else "sync"] += 1
                params, was_offloaded = _fetch_params(msg, store_dir)
                saw["offloaded"] += was_offloaded
                assert isinstance(params, dict) and "params" in params
                round_idx = int(msg.get("round_idx", 0))
                update = _delta_like(params, 0.01)  # "on-device training"
                phone.publish(uplink, wire_pack({
                    "msg_type": 3, "sender": 1, "receiver": 0,
                    "model_params": update, "num_samples": 10,
                    "round_idx": round_idx,
                }))
            elif mtype == 7:  # FINISH
                saw["finish"] += 1
                break
        assert not server_err, server_err
        assert saw["check"] == 1 and saw["init"] == 1
        assert saw["sync"] == args.comm_round - 1
        assert saw["finish"] == 1, f"no FINISH within deadline: {saw}"
        # the >8 KB offload path was actually exercised (mnist lr ~31 KB)
        assert saw["offloaded"] >= 1
        t.join(timeout=30)
        assert not t.is_alive(), "server did not stop after FINISH"
        # the server's round history is real: one record per round
        assert len(history) == args.comm_round, history
        # server persisted the aggregated global model file each round
        blob_path = str(tmp_path / "global.blob")
        assert os.path.exists(blob_path)
        final = wire_unpack(open(blob_path, "rb").read())
        # aggregate of one client's (init + 0.01K) params: every float leaf
        # moved by ~0.01 per round
        k0 = np.asarray(variables["params"]["linear"]["kernel"])
        k2 = np.asarray(final["params/linear/kernel"])
        np.testing.assert_allclose(
            k2, k0 + 0.01 * args.comm_round, rtol=0, atol=1e-5)
    finally:
        phone.close()
        broker.close()

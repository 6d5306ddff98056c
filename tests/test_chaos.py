"""Chaos suite: full cross-silo deployments under seeded fault plans.

Every test here is deterministic (hash-seeded fault draws, no wall-clock
randomness) and bounded (short round/handshake deadlines, thread joins with
timeouts) — a hang is a failure, never a stall of the suite.
"""

import collections
import queue
import threading
import time

import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.comm import LoopbackHub, Message
from fedml_tpu.comm.resilience import FaultPlan
from fedml_tpu.core import telemetry
from fedml_tpu.cross_silo import FedML_Horizontal, MyMessage
from fedml_tpu.cross_silo.chaos import run_chaos_drill

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.configure(enabled=True, reset=True)
    yield
    telemetry.configure(enabled=True, reset=True)


def _args(**kw):
    base = dict(
        dataset="mnist", model="lr", debug_small_data=True,
        client_num_in_total=2, client_num_per_round=2, comm_round=1,
        learning_rate=0.1, epochs=1, batch_size=8, frequency_of_the_test=1,
        random_seed=0,
    )
    base.update(kw)
    return fedml_tpu.init(config=base)


def _drain(q):
    out = []
    while True:
        try:
            data = q.get_nowait()
        except queue.Empty:
            return out
        if data is not None:
            out.append(Message.from_bytes(data))


def _online(sender):
    m = Message(MyMessage.MSG_TYPE_C2S_CLIENT_STATUS, sender, 0)
    m.add_params(MyMessage.MSG_ARG_KEY_CLIENT_STATUS,
                 MyMessage.MSG_CLIENT_STATUS_IDLE)
    return m


def _upload(server, sender, round_idx=0):
    import jax

    delta = jax.tree_util.tree_map(
        lambda x: np.zeros_like(np.asarray(x)),
        server.aggregator.get_global_model_params())
    m = Message(MyMessage.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, sender, 0)
    m.add_params(MyMessage.MSG_ARG_KEY_MODEL_PARAMS, delta)
    m.add_params(MyMessage.MSG_ARG_KEY_NUM_SAMPLES, 8)
    m.add_params(MyMessage.MSG_ARG_KEY_ROUND_INDEX, round_idx)
    return m


def _wait_for(predicate, timeout=10.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


# --- seeded drills (drop / crash / transient send failures) ------------------


def test_chaos_drill_packet_loss_completes_all_rounds():
    """20% of every message type dropped, every round — straggler timeouts
    and resends must still walk the run to completion."""
    result = run_chaos_drill(join_timeout_s=90.0)  # seeded drop-20% defaults
    assert result.ok, result.summary()
    assert result.rounds_completed == 3
    assert result.faults_injected.get("drop", 0) >= 1, result.summary()
    # the run didn't just terminate — it still trained something sane
    final = result.history[-1]
    assert np.isfinite(final.get("test_acc", np.nan)), final
    assert final["test_acc"] > 0.2, final


def test_chaos_drill_client_crash_completes_all_rounds():
    """One client dies at round 1 and stays dead — the round closes on the
    straggler timeout with the survivors and the run still finishes."""
    result = run_chaos_drill(join_timeout_s=90.0, fault_drop_rate=0.0,
                             fault_crash_rank=3, fault_crash_at_round=1)
    assert result.ok, result.summary()
    assert result.faults_injected.get("crash", 0) == 1, result.summary()


def test_chaos_drill_transient_send_failures_are_retried():
    result = run_chaos_drill(join_timeout_s=90.0, fault_drop_rate=0.0,
                             fault_fail_send_rate=0.3)
    assert result.ok, result.summary()
    assert result.send_retries >= 1, result.summary()
    assert result.faults_injected.get("fail_send", 0) >= 1, result.summary()


# --- server restart from the round-state checkpoint --------------------------


def test_chaos_server_restart_resumes_from_checkpoint(tmp_path):
    """Kill the server after round 0 (seeded crash plan), then boot a fresh
    server process on the same transport with the same checkpoint path: it
    must resume at round 1 — not round 0 — and finish the remaining rounds
    with the clients that never went away."""
    cfg = dict(
        dataset="mnist", model="lr", debug_small_data=True,
        client_num_in_total=2, client_num_per_round=2, comm_round=3,
        learning_rate=0.1, epochs=1, batch_size=8, frequency_of_the_test=1,
        random_seed=0,
        round_ckpt_path=str(tmp_path / "round_state.msgpack"),
        ckpt_every_rounds=1,
    )
    # phase 1: the incarnation that dies. The plan crashes rank 0 at round 1,
    # i.e. right after round 0 completes (and checkpoints) but before any
    # round-1 SYNC reaches a client.
    args_a = fedml_tpu.init(config={**cfg, "fault_crash_rank": 0,
                                    "fault_crash_at_round": 1})
    hub = LoopbackHub()
    server_a = FedML_Horizontal(args_a, 0, 2, backend="LOOPBACK", hub=hub)
    clients = [FedML_Horizontal(args_a, rank, 2, backend="LOOPBACK", hub=hub)
               for rank in (1, 2)]
    client_threads = [threading.Thread(target=c.run, daemon=True)
                      for c in clients]
    for t in client_threads:
        t.start()
    server_a.start()
    thread_a = threading.Thread(target=server_a.run, daemon=True)
    thread_a.start()
    thread_a.join(timeout=60)
    assert not thread_a.is_alive(), "crashed server's loop did not exit"
    assert len(server_a.history) == 1  # died after exactly one round
    assert server_a.com_manager.crashed

    # phase 2: a fresh server process (no fault plan) on the same hub + path.
    # A real restart binds a fresh endpoint; here the hub queue is shared
    # between incarnations, so clear the dead server's leftover poison pill.
    stale = hub.register(0)
    while not stale.empty():
        stale.get_nowait()
    args_b = fedml_tpu.init(config=cfg)
    server_b = FedML_Horizontal(args_b, 0, 2, backend="LOOPBACK", hub=hub)
    assert server_b.round_idx == 1  # resumed, not restarted
    thread_b = threading.Thread(target=server_b.run, daemon=True)
    thread_b.start()
    server_b.start()  # re-probes; the still-running clients answer ONLINE
    thread_b.join(timeout=90)
    assert not thread_b.is_alive(), "resumed server did not finish"
    assert [h["round"] for h in server_b.history] == [1, 2]
    assert server_b.round_idx == 3
    for t in client_threads:
        t.join(timeout=10)
        assert not t.is_alive()
    # clients followed the resumed numbering from the round-stamped INIT
    assert all(c.round_idx == 2 for c in clients)


# --- rejoin + handshake deadline (server FSM, driven synchronously) ----------


def test_chaos_midrun_online_report_gets_current_sync():
    """A client that restarts mid-round re-announces ONLINE; the server's
    rejoin path answers with the CURRENT round's model instead of leaving it
    idle until FINISH."""
    args = _args(comm_round=2)
    hub = LoopbackHub()
    server = FedML_Horizontal(args, 0, 2, backend="LOOPBACK", hub=hub)
    server.register_message_receive_handlers()
    server.start()
    server.receive_message(MyMessage.MSG_TYPE_C2S_CLIENT_STATUS, _online(1))
    server.receive_message(MyMessage.MSG_TYPE_C2S_CLIENT_STATUS, _online(2))
    assert server.is_initialized
    before = _drain(hub.register(1))
    assert [m.get_type() for m in before] == [
        MyMessage.MSG_TYPE_S2C_CHECK_CLIENT_STATUS,
        MyMessage.MSG_TYPE_S2C_INIT_CONFIG,
    ]
    # mid-round restart: the client lost its state and announces again
    server.receive_message(MyMessage.MSG_TYPE_C2S_CLIENT_STATUS, _online(1))
    rejoin = _drain(hub.register(1))
    assert [m.get_type() for m in rejoin] == [
        MyMessage.MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT]
    assert rejoin[0].get(MyMessage.MSG_ARG_KEY_ROUND_INDEX) == 0
    assert rejoin[0].get(MyMessage.MSG_ARG_KEY_MODEL_PARAMS) is not None
    # once its upload is in, a further ONLINE is a no-op (nothing to redo)
    server.receive_message(MyMessage.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER,
                           _upload(server, 1))
    server.receive_message(MyMessage.MSG_TYPE_C2S_CLIENT_STATUS, _online(1))
    assert _drain(hub.register(1)) == []


def test_chaos_handshake_deadline_drops_silent_clients():
    """The all-online barrier must not wait forever: after the handshake
    deadline the cohort is re-selected from whoever reported ONLINE."""
    args = _args(handshake_timeout=0.3, min_clients_per_round=1)
    hub = LoopbackHub()
    server = FedML_Horizontal(args, 0, 2, backend="LOOPBACK", hub=hub)
    server.register_message_receive_handlers()
    server.start()
    server.receive_message(MyMessage.MSG_TYPE_C2S_CLIENT_STATUS, _online(1))
    assert not server.is_initialized  # client 2 still silent
    assert _wait_for(lambda: server.is_initialized, timeout=10.0)
    assert server.client_id_list_in_this_round == [1]
    assert len(server.data_silo_index_list) == 1
    types_1 = [m.get_type() for m in _drain(hub.register(1))]
    assert MyMessage.MSG_TYPE_S2C_INIT_CONFIG in types_1
    # the silent client only ever saw status probes — never an INIT
    types_2 = {m.get_type() for m in _drain(hub.register(2))}
    assert types_2 == {MyMessage.MSG_TYPE_S2C_CHECK_CLIENT_STATUS}


def test_chaos_handshake_deadline_reprobes_below_min_clients():
    """Below min_clients the deadline must NOT start the round — it re-probes
    the silent clients and re-arms instead."""
    args = _args(handshake_timeout=0.2, min_clients_per_round=2)
    hub = LoopbackHub()
    server = FedML_Horizontal(args, 0, 2, backend="LOOPBACK", hub=hub)
    server.register_message_receive_handlers()
    server.start()
    server.receive_message(MyMessage.MSG_TYPE_C2S_CLIENT_STATUS, _online(1))
    probes = hub.register(2)
    baseline = probes.qsize()  # the initial CHECK
    assert _wait_for(lambda: probes.qsize() > baseline, timeout=10.0)
    assert not server.is_initialized
    # the missing client finally answers: the normal barrier fires
    server.receive_message(MyMessage.MSG_TYPE_C2S_CLIENT_STATUS, _online(2))
    assert server.is_initialized
    server._arm_handshake_timer()  # no-op once initialized — nothing re-arms


# --- round-timeout extend path (satellite) -----------------------------------


def test_chaos_round_timeout_extends_below_min_then_closes():
    """Timeout with fewer than min_clients uploads must extend the round
    (re-arming the timer and re-offering the model to silent clients), then
    close normally once the threshold is met."""
    args = _args(round_timeout=0.3, min_clients_per_round=2)
    hub = LoopbackHub()
    server = FedML_Horizontal(args, 0, 2, backend="LOOPBACK", hub=hub)
    server.register_message_receive_handlers()
    server.start()
    server.receive_message(MyMessage.MSG_TYPE_C2S_CLIENT_STATUS, _online(1))
    server.receive_message(MyMessage.MSG_TYPE_C2S_CLIENT_STATUS, _online(2))
    q1, q2 = hub.register(1), hub.register(2)
    _drain(q1), _drain(q2)  # CHECK + INIT for both

    server.receive_message(MyMessage.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER,
                           _upload(server, 1))
    # 1/2 uploads < min 2: the deadline extends instead of closing
    assert _wait_for(lambda: q2.qsize() > 0, timeout=10.0)
    assert server.history == []  # round still open
    assert server._timer is not None  # timer re-armed
    resent = _drain(q2)
    assert {m.get_type() for m in resent} == {
        MyMessage.MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT}
    assert resent[0].get(MyMessage.MSG_ARG_KEY_ROUND_INDEX) == 0
    assert _drain(q1) == []  # the client that already uploaded gets nothing

    # threshold met -> the round closes (and, at comm_round=1, finishes)
    server.receive_message(MyMessage.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER,
                           _upload(server, 2))
    assert len(server.history) == 1
    finish_types = [m.get_type() for m in _drain(q1)]
    assert finish_types == [MyMessage.MSG_TYPE_S2C_FINISH]


# --- byte parity with faults disabled ----------------------------------------


class RecordingHub(LoopbackHub):
    """Loopback hub that keeps a per-rank multiset of every payload posted —
    the transcript two runs are compared by."""

    def __init__(self):
        super().__init__()
        self.posted = collections.defaultdict(collections.Counter)

    def post(self, rank, data):
        if data is not None:
            self.posted[rank][bytes(data)] += 1
        super().post(rank, data)


def _recorded_run(**extra):
    # telemetry off: trace stamps are uuid-random and would (correctly)
    # differ between otherwise-identical runs
    args = _args(comm_round=2, telemetry_enabled=False, **extra)
    hub = RecordingHub()
    server = FedML_Horizontal(args, 0, 2, backend="LOOPBACK", hub=hub)
    clients = [FedML_Horizontal(args, rank, 2, backend="LOOPBACK", hub=hub)
               for rank in (1, 2)]
    threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
    for t in threads:
        t.start()
    server.start()
    server.run()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert len(server.history) == 2
    return {rank: dict(c) for rank, c in hub.posted.items()}


def test_chaos_disabled_fault_config_is_byte_identical():
    """`fault_*` keys present but zero/unset must leave the message flow
    byte-identical to a config without them (acceptance criterion: disabled
    chaos is not a behavior change)."""
    disabled = dict(fault_seed=11, fault_drop_rate=0.0,
                    fault_fail_send_rate=0.0, fault_delay_rate=0.0)
    assert FaultPlan.from_args(_args(**disabled)) is None  # no wrapper at all
    baseline = _recorded_run()
    with_keys = _recorded_run(**disabled)
    assert baseline == with_keys


def test_chaos_sync_duplicate_upload_commits_once():
    """A client that re-sends its round upload (it rejoined mid-round after
    already sending) must not advance the barrier or double-count in the
    fold — slot-keyed uploads make duplicates structurally idempotent."""
    args = _args(comm_round=1)
    hub = LoopbackHub()
    server = FedML_Horizontal(args, 0, 2, backend="LOOPBACK", hub=hub)
    server.register_message_receive_handlers()
    server.start()
    server.receive_message(MyMessage.MSG_TYPE_C2S_CLIENT_STATUS, _online(1))
    server.receive_message(MyMessage.MSG_TYPE_C2S_CLIENT_STATUS, _online(2))
    server.receive_message(MyMessage.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER,
                           _upload(server, 1))
    assert server.history == []  # round open, waiting on client 2
    server.receive_message(MyMessage.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER,
                           _upload(server, 1))  # duplicate
    assert server.history == []  # the duplicate must NOT close the barrier
    assert server.aggregator.received_count == 1
    server.receive_message(MyMessage.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER,
                           _upload(server, 2))
    assert len(server.history) == 1
    # a post-commit re-send of the same round is stale and ignored
    server.receive_message(MyMessage.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER,
                           _upload(server, 1))
    assert len(server.history) == 1


def test_chaos_async_server_restart_no_duplicate_commits(tmp_path):
    """Async (FedBuff-style) server dies mid-run and restarts from the
    round-state checkpoint while its free-running clients keep going. The
    in-flight uploads that raced the crash are replayed to the fresh
    incarnation AND re-sent by the rejoining clients — the per-sender
    sequence numbers resumed from the checkpoint must commit every update
    exactly once, and the version log must stay retention-bounded."""
    cfg = dict(
        dataset="mnist", model="lr", debug_small_data=True,
        client_num_in_total=2, client_num_per_round=2, comm_round=4,
        learning_rate=0.1, epochs=1, batch_size=8, frequency_of_the_test=1,
        random_seed=0, async_mode=True, async_buffer_size=2,
        round_ckpt_path=str(tmp_path / "round_state.msgpack"),
        ckpt_every_rounds=1, round_store_keep_versions=2,
    )
    # phase 1: the incarnation that dies once it touches version-2 traffic —
    # after at least one commit is checkpointed, before the run finishes.
    args_a = fedml_tpu.init(config={**cfg, "fault_crash_rank": 0,
                                    "fault_crash_at_round": 2})
    hub = LoopbackHub()
    server_a = FedML_Horizontal(args_a, 0, 2, backend="LOOPBACK", hub=hub)
    clients = [FedML_Horizontal(args_a, rank, 2, backend="LOOPBACK", hub=hub)
               for rank in (1, 2)]
    client_threads = [threading.Thread(target=c.run, daemon=True)
                      for c in clients]
    for t in client_threads:
        t.start()
    server_a.start()
    thread_a = threading.Thread(target=server_a.run, daemon=True)
    thread_a.start()
    thread_a.join(timeout=60)
    assert not thread_a.is_alive(), "crashed server's loop did not exit"
    assert server_a.com_manager.crashed
    assert 1 <= server_a.model_version < 4  # died mid-run, post-commit

    # phase 2: fresh incarnation on the same hub + checkpoint. The dead
    # server's queue holds the uploads that raced the crash — replay them
    # (real transports redeliver; the rejoining clients will ALSO re-send
    # theirs after the resumed INIT, so both duplicate paths are exercised).
    stale = hub.register(0)
    in_flight = []
    while not stale.empty():
        data = stale.get_nowait()
        if data is not None:
            m = Message.from_bytes(data)
            if m.get_type() == MyMessage.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER:
                in_flight.append(data)
    args_b = fedml_tpu.init(config=cfg)
    server_b = FedML_Horizontal(args_b, 0, 2, backend="LOOPBACK", hub=hub)
    assert server_b.model_version == server_a.model_version  # resumed
    assert server_b.committed_updates == 2 * server_a.model_version
    for data in in_flight:
        hub.post(0, data)
    thread_b = threading.Thread(target=server_b.run, daemon=True)
    thread_b.start()
    server_b.start()  # re-probes; the still-running clients answer ONLINE
    thread_b.join(timeout=90)
    assert not thread_b.is_alive(), "resumed server did not finish"
    for t in client_threads:
        t.join(timeout=10)
        assert not t.is_alive()

    # exactly-once across both incarnations: 4 commits of K=2, no update
    # lost to the crash and none committed twice despite the replays
    assert server_b.model_version == 4
    assert server_b.committed_updates == 8
    assert server_b.shed_updates == 0
    # every commit folded exactly K updates (a free-running client may land
    # two consecutive sequences in one commit — that is not a duplicate;
    # exactly-once is per (sender, sequence), pinned by the totals above)
    assert all(e[1] == 2 and len(e[2]) == 2 for e in server_b._version_log)
    # retention: the log carries only the last keep_versions commits
    assert [e[0] for e in server_b._version_log] == [3, 4]


# --- hierarchical-federation drills (leaf crash / partition) ------------------


def test_tier_drill_leaf_crash_exactly_once():
    """A leaf aggregator killed mid-generation (shard persisted, upload
    lost): the root must rehydrate the dead leaf's chunk, every client's
    update commits exactly once, and the final model matches the fault-free
    reference within the accuracy gate."""
    from fedml_tpu.cross_silo.chaos import run_tier_drill

    result = run_tier_drill(scenario="leaf_crash")
    assert result.ok, result.summary()
    assert result.failovers == 1
    assert result.rehydrations == 1
    assert result.duplicate_commits == 0
    assert result.committed_updates == result.expected_updates
    rec = result.json_record()
    assert rec["ok"] and rec["scenario"] == "leaf_crash"


def test_tier_drill_partition_heals():
    """A root<->leaf cut for one round window: the orphaned chunk recomputes
    on a survivor (no shard store in this drill), the cut heals after the
    window, and the exactly-once + accuracy gates hold."""
    from fedml_tpu.cross_silo.chaos import run_tier_drill

    result = run_tier_drill(scenario="partition")
    assert result.ok, result.summary()
    assert result.failovers == 1
    assert result.rehydrations == 0  # no shard dir -> recompute path
    assert result.duplicate_commits == 0
    assert result.faults_injected.get("partition", 0) >= 1


# --- version-log retention boundary (tiered plane, satellite) ----------------


def test_tier_version_log_retention_resume_is_bit_exact(tmp_path):
    """Resume a tiered run from a checkpoint taken PAST the version-log
    retention boundary (more commits than keep_versions): the resumed run
    must finish bit-identical to an uninterrupted one, and the trimmed log
    must keep exactly the last-N window through the restart."""
    import jax

    from fedml_tpu.simulation.federation import build_tiered_simulator

    cfg = dict(
        dataset="mnist", model="lr", debug_small_data=True,
        client_num_in_total=6, client_num_per_round=4, comm_round=5,
        learning_rate=0.05, epochs=1, batch_size=8, frequency_of_the_test=1,
        random_seed=0, hier_num_leaves=2, group_comm_round=2,
        round_store_keep_versions=2,
    )
    ref, _ = build_tiered_simulator(fedml_tpu.init(config=cfg))
    ref.run(None, log_fn=None)
    assert [e[0] for e in ref.state.version_log] == [4, 5]  # trimmed to 2

    ckpt = str(tmp_path / "tier_state.msgpack")
    part, _ = build_tiered_simulator(fedml_tpu.init(
        config={**cfg, "comm_round": 3, "round_ckpt_path": ckpt}))
    part.run(None, log_fn=None)
    # 3 commits > keep 2: the checkpointed log already lost version 1
    assert [e[0] for e in part.state.version_log] == [2, 3]

    resumed, _ = build_tiered_simulator(fedml_tpu.init(
        config={**cfg, "round_ckpt_path": ckpt}))
    assert resumed.state.start_round == 3
    assert resumed.state.model_version == 3
    assert [e[0] for e in resumed.state.version_log] == [2, 3]
    resumed.run(None, log_fn=None)
    assert [e[0] for e in resumed.state.version_log] == [4, 5]

    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(ref.params)),
                    jax.tree_util.tree_leaves(
                        jax.device_get(resumed.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_straggler_drill_gates_goodput_and_accuracy():
    """The buffered-async straggler drill (PR 14 acceptance): under 10×
    seeded heavy-tail skew the async engine's goodput (committed updates
    per virtual second) must beat the synchronous round rate ≥3× with
    final accuracy within 2% of the sync run — and the drill's json_record
    must carry the gate verdicts (``fedml-tpu chaos-drill --straggler
    --json`` prints it)."""
    from fedml_tpu.cross_silo.chaos import run_straggler_drill

    result = run_straggler_drill()
    assert result.ok, result.summary()
    assert result.goodput_ratio >= 3.0
    assert abs(result.acc_delta) <= 0.02
    rec = result.json_record()
    assert rec["ok"] and rec["goodput_ratio"] >= 3.0

"""Benchmark: FedAvg CIFAR-10 ResNet-56 rounds/sec (BASELINE.json north star).

Setup mirrors the reference MPI benchmark config (BENCHMARK_MPI.md: 100-client
pool, 10 clients/round, batch 64) with 1 local epoch per round.

Measurement protocol:
- a warm run over the SAME round range as a timed block pays compile +
  device-data upload (discarded) — sampling is round-indexed, so the warm
  run compiles exactly the cohort shapes the timed blocks will replay,
- then 5 independent timed runs ("blocks") of N rounds each (after one
  discarded burn-in block), measured
  WALL-TO-WALL around sim.run(): run() ends by materializing the final
  round's metric vector, whose value requires every dispatched executable
  to have retired. The reported value is the MEDIAN block rate; the spread
  (max-min) is printed on stderr so one-shot flukes are visible.
- before timing, the forward computation is lowered and asserted to contain
  bf16 ops (mixed precision actually engaged, not just requested).

Device: the measurement runs in THIS process on the first ``jax.devices()``
entry, which must be a TPU — anything else prints the JSON line with an
"error" field (value null) and exits 1, so the bench never measures a CPU
and the driver artifact always parses. Every line is stamped with the
platform, device kind and device count it ran on. One process holds the
chip: nothing here starts a subprocess.

Baseline denominator: the reference publishes no wall-clock numbers
(BASELINE.md). If ``BASELINE_LOCAL.json`` exists (produced by
``scripts/measure_reference_baseline.py`` — the reference's torch hot loop
timed on THIS machine's CPU at the same workload and extrapolated to a
round), its rounds/sec is used and the basis is echoed in the output line.
Otherwise vs_baseline falls back to a denominator of 1.0 round/sec with
basis "undocumented-1.0" — explicitly a placeholder, not a measurement.
"""

from __future__ import annotations

import json
import os
import sys
import time


def device_stamp() -> dict:
    """What this process computes on, as JAX reports it. Initialises the
    backend; a platform that cannot start raises RuntimeError."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def emit(value, vs_baseline, basis, error=None, candidate_errors=None,
         host_pack=None, telemetry=None, device=None) -> None:
    line = {
        "metric": "fedavg_cifar10_resnet56_rounds_per_sec",
        "value": value,
        "unit": ("rounds/sec (10 clients x 1 epoch x bs64 per round; "
                 f"baseline basis: {basis})"),
        "vs_baseline": vs_baseline,
        **(device or {}),
    }
    if error is not None:
        line["error"] = error
    if candidate_errors:
        # a one-executor run is a DEGRADED measurement, not a clean A/B
        # win — automation must be able to tell them apart (main also
        # exits non-zero)
        line["candidate_errors"] = {
            ("flat" if k else "tree"): v for k, v in candidate_errors.items()
        }
    if host_pack:
        # per-round host-packing attribution from the final timed block
        # (pack_time = build cost wherever it ran, pack_wait = round-loop
        # stall, overlap = fraction hidden behind earlier device work)
        line["host_pack"] = host_pack
    if telemetry:
        # phase breakdown + metrics-registry snapshot of the final timed
        # block (fedml_tpu.core.telemetry) — where the round wall went
        line["telemetry"] = telemetry
    print(json.dumps(line), flush=True)


def _host_pack_stats(history) -> dict:
    recs = [r for r in history if "pack_time" in r]
    if not recs:
        return {}
    mean = lambda k: sum(r[k] for r in recs) / len(recs)  # noqa: E731
    return {
        "pack_time_mean_s": round(mean("pack_time"), 6),
        "pack_wait_mean_s": round(mean("pack_wait"), 6),
        "overlap_mean": round(mean("overlap"), 4),
    }


def _phase_stats(history) -> dict:
    """Mean per-round phase attribution over a run's history: where the
    round wall-clock went (device wait vs dispatch vs eval vs host slack)
    and how much of it the named phases cover (coverage_frac ~1.0 — the
    accumulator is drained at the same stamp round_time is taken)."""
    recs = [r for r in history if r.get("phases")]
    if not recs:
        return {}
    acc: dict = {}
    for r in recs:
        for k, v in r["phases"].items():
            acc[k] = acc.get(k, 0.0) + v
    n = len(recs)
    round_mean = sum(r["round_time"] for r in recs) / n
    covered = sum(acc.values()) / n
    return {
        "round_time_mean_s": round(round_mean, 6),
        "coverage_frac": round(covered / round_mean, 4) if round_mean else None,
        "phase_breakdown_s": {k: round(v / n, 6) for k, v in acc.items()},
    }


def load_baseline() -> tuple[float, str]:
    baseline_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "BASELINE_LOCAL.json")
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            base = json.load(f)
        return float(base["rounds_per_sec"]), base.get("basis",
                                                       "BASELINE_LOCAL.json")
    return 1.0, "undocumented-1.0"


# one block = one sim.run() = this many rounds; _build's comm_round and
# the timed-block rate numerator must be THIS constant or the metric
# silently corrupts (the rate divides ROUNDS_PER_BLOCK by a run's wall)
ROUNDS_PER_BLOCK = 6


def _build(flat: bool):
    import jax
    import jax.numpy as jnp

    import fedml_tpu
    from fedml_tpu.simulation import build_simulator

    # Lane count: per-step cost of the lane scan grows with the lane count
    # under tree carry (one small op per leaf per lane); 2 is the constant
    # carried over from earlier rounds and is not measured on this chip.
    # Override with FEDML_BENCH_LANES.
    lanes_env = os.environ.get("FEDML_BENCH_LANES", "2")
    args = fedml_tpu.init(config=dict(
        dataset="cifar10", model="resnet56", partition_method="hetero",
        partition_alpha=0.5, client_num_in_total=100, client_num_per_round=10,
        comm_round=ROUNDS_PER_BLOCK, learning_rate=0.01, epochs=1,
        batch_size=64, frequency_of_the_test=10_000, random_seed=0,
        use_bf16=True,
        packed_lanes=int(lanes_env) if lanes_env else None,
        packed_flat_carry=flat,
    ))
    sim, apply_fn = build_simulator(args)
    assert sim._use_device_data, "device-resident data path must engage"
    # Dirichlet alpha=0.5 client sizes are heavily skewed: the auto cohort
    # schedule must pick the packed-lane path (one program per round,
    # clients back-to-back in balanced lanes — 2.1x over bucketed)
    assert sim._packed, "packed cohort schedule must engage on skewed data"

    # mixed precision must actually engage: the lowered forward has bf16 ops
    x_probe = jnp.zeros((8, 32, 32, 3), jnp.float32)
    hlo = jax.jit(
        lambda p, x: apply_fn(p, x, train=True)
    ).lower(sim.params, x_probe).as_text()
    assert "bf16" in hlo, "bf16 requested but absent from lowered HLO"
    return sim


def _timed_block(sim, rounds_per_block: int) -> float:
    sim.history.clear()
    t0 = time.perf_counter()
    sim.run(apply_fn=None, log_fn=None)
    return rounds_per_block / (time.perf_counter() - t0)


def run_bench() -> tuple[float, dict, dict]:
    blocks, rounds_per_block = 5, ROUNDS_PER_BLOCK
    # Carry selection: flat carry (lane scan state as ONE ravelled vector)
    # is parity-exact vs tree (tests/test_packed_schedule.py); which is
    # faster end to end is not measured on this chip, so both executors
    # are warmed and the faster one runs the timed blocks. Schedule choice
    # is the framework's job — the metric is achievable rounds/sec.
    # FEDML_BENCH_FLAT={0,1} pins a carry and skips the A/B.
    forced = os.environ.get("FEDML_BENCH_FLAT", "")
    flats = ((forced == "1",) if forced in ("0", "1") else (True, False))
    cands, warm, errors = {}, {}, {}
    for flat in flats:
        # a candidate that fails to build/compile/run is recorded and the
        # survivor is still measured, so the line carries a number — but
        # main() exits non-zero: a failed executor is a failed run
        try:
            sim = _build(flat)
            sim.run(apply_fn=None, log_fn=None)   # compile + upload
            _timed_block(sim, rounds_per_block)   # burn-in (discarded)
            # decide on a MEDIAN of 3 warm blocks — one-shot block rates
            # fluke (that is why the timed phase prints its spread)
            rates = sorted(_timed_block(sim, rounds_per_block)
                           for _ in range(3))
        except Exception as e:  # noqa: BLE001
            errors[flat] = f"{type(e).__name__}: {e}"
            print(f"carry candidate flat={flat} FAILED: {errors[flat]}",
                  file=sys.stderr, flush=True)
            continue
        cands[flat] = sim
        warm[flat] = rates[1]
        print(f"warm blocks: flat={flat} {[round(r, 3) for r in rates]} "
              f"median={warm[flat]:.4f} r/s", file=sys.stderr, flush=True)
    if not cands:
        raise RuntimeError(f"every carry candidate failed: {errors}")
    flat = max(warm, key=warm.get)
    sim = cands.pop(flat)
    cands.clear()  # drop the loser's device-resident data before timing
    print(f"carry selected: {'flat' if flat else 'tree'}",
          file=sys.stderr, flush=True)

    from fedml_tpu.core import telemetry as _telemetry

    _telemetry.get_registry().reset()  # snapshot covers the timed blocks only
    block_rates = sorted(
        _timed_block(sim, rounds_per_block) for _ in range(blocks))
    rounds_per_sec = block_rates[len(block_rates) // 2]
    spread = block_rates[-1] - block_rates[0]
    print(
        f"block rates: {[round(r, 3) for r in block_rates]} "
        f"median={rounds_per_sec:.4f} spread={spread:.4f}",
        file=sys.stderr,
    )
    telemetry_stats = {
        **_phase_stats(sim.history),
        "registry": _telemetry.get_registry().snapshot(),
    }
    # history of the LAST timed block (each block clears it first)
    return (rounds_per_sec, errors, _host_pack_stats(sim.history),
            telemetry_stats)


def main() -> int:
    try:
        baseline, basis = load_baseline()
        if baseline <= 0:
            raise ValueError(f"non-positive baseline {baseline}")
    except Exception as e:  # noqa: BLE001 — never lose the JSON line
        baseline, basis = 1.0, f"undocumented-1.0 (baseline unreadable: {e})"
    from fedml_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    try:
        device = device_stamp()
    except RuntimeError as e:
        emit(None, None, basis, error=f"no backend: {e}")
        return 1
    if device["platform"] != "tpu":
        emit(None, None, basis, device=device,
             error=f"platform is {device['platform']!r}, not 'tpu': this "
                   "benchmark only measures the chip")
        return 1
    try:
        rounds_per_sec, candidate_errors, host_pack, telem = run_bench()
    except Exception as e:  # noqa: BLE001 — driver artifact must parse
        emit(None, None, basis, error=f"{type(e).__name__}: {e}",
             device=device)
        return 1
    emit(round(rounds_per_sec, 4), round(rounds_per_sec / baseline, 4), basis,
         candidate_errors=candidate_errors, host_pack=host_pack,
         telemetry=telem, device=device)
    return 1 if candidate_errors else 0


def host_pack_bench(rounds: int = 20) -> int:
    """``--host-pack``: CPU-only micro-mode isolating the per-round HOST
    packing cost of the packed schedule (100-client Dirichlet cohort, full
    participation). Times the vectorized builder (cohort-level pack + cached
    lane plan + native row gather) against the pre-pipeline per-client loop
    on identical inputs — the builders are bit-exact (tests/test_prefetch.py)
    so this is a pure like-for-like host cost A/B. No chip probe: the win is
    measurable wherever python runs, which is the point (the device never
    waits on a host that packs ahead). Also runs a short prefetch-on block
    and reports the recorded overlap fraction."""
    import numpy as np

    import fedml_tpu
    from fedml_tpu.simulation import build_simulator
    from fedml_tpu.simulation.fed_sim import reference_client_sampling

    args = fedml_tpu.init(config=dict(
        dataset="cifar10", model="lr", partition_method="hetero",
        partition_alpha=0.5, client_num_in_total=100,
        client_num_per_round=100, comm_round=4, learning_rate=0.05,
        epochs=1, batch_size=16, frequency_of_the_test=10_000,
        random_seed=0, debug_small_data=True, cohort_schedule="packed",
    ))
    sim, _ = build_simulator(args)
    assert sim._packed, "packed cohort schedule must engage"
    cfg = sim.cfg
    cohorts = [
        np.asarray(reference_client_sampling(
            r, cfg.client_num_in_total, cfg.client_num_per_round))
        for r in range(rounds)
    ]
    # steady state on both sides: lane-plan cache warm for the new builder
    # (the loop has no cache to warm — it redoes everything every round)
    sim._build_packed_inputs(cohorts[0], 0, None)
    t_new, t_old = [], []
    for r, ci in enumerate(cohorts):
        t0 = time.perf_counter()
        sim._build_packed_inputs(ci, r, None)
        t_new.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        sim._build_packed_inputs_loop(ci, r, None)
        t_old.append(time.perf_counter() - t0)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    new_s, old_s = med(t_new), med(t_old)
    hist = sim.run(apply_fn=None, log_fn=None)  # prefetch defaults on
    overlap = _host_pack_stats(hist)
    line = {
        "metric": "host_pack_packed_round_build_seconds",
        "unit": ("median s/round host packing, 100-client Dirichlet(0.5) "
                 "cohort, packed schedule, full participation"),
        "value": round(new_s, 6),
        "loop_baseline": round(old_s, 6),
        "speedup": round(old_s / new_s, 2) if new_s > 0 else None,
        **({"host_pack": overlap} if overlap else {}),
    }
    print(json.dumps(line), flush=True)
    ok = new_s > 0 and old_s / new_s >= 2.0 and \
        overlap.get("overlap_mean", 0.0) > 0.0
    print(f"host-pack: new={new_s * 1e3:.2f}ms loop={old_s * 1e3:.2f}ms "
          f"speedup={old_s / new_s:.2f}x "
          f"overlap_mean={overlap.get('overlap_mean')} "
          f"{'OK' if ok else 'BELOW TARGET'}", file=sys.stderr, flush=True)
    return 0 if ok else 1


def cohort_sweep_bench(sizes=(10, 100, 1000, 10000), pool: int = 20000,
                       warmup_rounds: int = 2, measured_rounds: int = 3) -> int:
    """``--cohort-sweep``: CPU-only scaling sweep of the arena-backed round
    loop over sampled cohort sizes (10/100/1k/10k from a 20k-client pool,
    SCAFFOLD so every round exercises the client-state gather/scatter path).
    Synthetic separable 2-class blobs keep the per-client work constant so
    the sweep isolates cohort-axis scaling: per size it reports rounds/sec
    plus the per-round phase breakdown (state_gather / state_scatter now
    attributed) and checks the named phases + host_other sum to round_time.
    Gate: the 10k-cohort sampled round must clear 1 round/sec."""
    import math

    import numpy as np

    import fedml_tpu
    from fedml_tpu.data.federated import ArrayPair, build_federated_data
    from fedml_tpu.simulation import build_simulator

    spc, dim, class_num = 8, 16, 2
    rng = np.random.default_rng(0)
    n = pool * spc
    y = (np.arange(n) % class_num).astype(np.int64)
    x = rng.normal(size=(n, dim)).astype(np.float32) \
        + 2.0 * y[:, None].astype(np.float32)
    net_map = {c: list(range(c * spc, (c + 1) * spc)) for c in range(pool)}
    fed = build_federated_data(
        ArrayPair(x, y), ArrayPair(x[:64], y[:64]), net_map, class_num)

    results = []
    for per_round in sizes:
        args = fedml_tpu.init(config=dict(
            dataset="synthetic_blobs", model="lr",
            client_num_in_total=pool, client_num_per_round=int(per_round),
            comm_round=warmup_rounds + measured_rounds,
            learning_rate=0.1, epochs=1, batch_size=spc,
            frequency_of_the_test=10_000, random_seed=0,
            federated_optimizer="SCAFFOLD",
            # synchronous rounds: with the prefetch pipeline on, round r+1's
            # host work lands in round r's drain window and the per-round
            # phase breakdown can exceed that round's wall; sync mode keeps
            # every phase inside its own round so the sum is exact
            prefetch=False,
        ))
        sim, _ = build_simulator(args, fed_data=fed)
        assert sim._arena is not None, "sweep must run the arena backend"
        hist = sim.run(apply_fn=None, log_fn=None)
        recs = hist[warmup_rounds:]
        wall = sum(r["round_time"] for r in recs)
        acc: dict = {}
        sums_ok = True
        for r in recs:
            ps = r["phases"]
            # host_other is computed as the exact remainder at drain time,
            # so the breakdown must reproduce round_time to float precision
            sums_ok = sums_ok and math.isclose(
                sum(ps.values()), r["round_time"],
                rel_tol=1e-6, abs_tol=1e-9)
            for k, v in ps.items():
                acc[k] = acc.get(k, 0.0) + v
        results.append({
            "cohort": int(per_round),
            "rounds_per_sec": round(measured_rounds / wall, 4) if wall else None,
            "phase_breakdown_s": {
                k: round(v / measured_rounds, 6) for k, v in sorted(acc.items())},
            "phase_sum_equals_round_time": bool(sums_ok),
            "state_phases_present": bool(
                "state_gather" in acc and "state_scatter" in acc),
        })
        print(f"cohort-sweep: cohort={per_round} "
              f"rounds_per_sec={results[-1]['rounds_per_sec']}",
              file=sys.stderr, flush=True)
    by_cohort = {r["cohort"]: r for r in results}
    pass_10k = (by_cohort.get(10000, {}).get("rounds_per_sec") or 0.0) > 1.0
    all_sums = all(r["phase_sum_equals_round_time"] for r in results)
    all_state = all(r["state_phases_present"] for r in results)
    line = {
        "metric": "cohort_sweep_rounds_per_sec",
        "unit": (f"rounds/sec per sampled cohort size, SCAFFOLD lr on "
                 f"synthetic blobs ({pool}-client pool, {spc} samples x "
                 f"dim {dim} each), arena client-state backend, CPU"),
        "results": results,
        "pass_10k_above_1rps": bool(pass_10k),
        "phase_sums_exact": bool(all_sums),
    }
    print(json.dumps(line), flush=True)
    ok = pass_10k and all_sums and all_state
    print(f"cohort-sweep: 10k>1r/s={pass_10k} phase_sums_exact={all_sums} "
          f"state_phases={all_state} {'OK' if ok else 'BELOW TARGET'}",
          file=sys.stderr, flush=True)
    return 0 if ok else 1


def agg_sweep_bench(cohorts=(1000, 10000), codecs=("none", "q4"),
                    defenses=("krum",), pool: int = 12000,
                    warmup_rounds: int = 1, measured_rounds: int = 2) -> int:
    """``--agg-sweep``: robust-aggregation frontier — defense x codec x
    cohort, each cell run with ``agg_kernels`` off (the unfused programs)
    and on (the fused quantize+pack / sanitize+Krum hot path), reporting
    rounds/sec, the exact per-phase attribution, and the codec's wire
    bytes per round (``spec_wire_nbytes`` x cohort). A second block
    measures the double-buffered arena movement: the residual
    ``state_gather + state_scatter`` cost under the prefetch pipeline
    (where ``put_take`` fuses scatter-back with the next round's gather,
    stamped ``state_move``) against the unoverlapped cost of the
    synchronous path.

    Gates: every phase breakdown must sum exactly to its round's wall
    time; the overlapped gather+scatter residual must be <= 20% of the
    unoverlapped cost; and on TPU (where the Pallas kernels engage — on
    CPU they fall back to the bit-identical jnp references, so the fused
    path's arithmetic is the same XLA code) the 10k-cohort krum+q4 cell
    must clear 2x the unfused rounds/sec."""
    import math

    import numpy as np

    import jax
    import fedml_tpu
    from fedml_tpu.comm.codec import spec_wire_nbytes
    from fedml_tpu.data.federated import ArrayPair, build_federated_data
    from fedml_tpu.simulation import build_simulator

    # dim 64 keeps the lr weight leaf above the codec's _MIN_LEAF
    # compressibility floor, so the wire-byte column actually shrinks
    # under q4 instead of every leaf riding raw
    spc, dim, class_num = 8, 64, 2
    rng = np.random.default_rng(0)
    n = pool * spc
    y = (np.arange(n) % class_num).astype(np.int64)
    x = rng.normal(size=(n, dim)).astype(np.float32) \
        + 2.0 * y[:, None].astype(np.float32)
    net_map = {c: list(range(c * spc, (c + 1) * spc)) for c in range(pool)}
    fed = build_federated_data(
        ArrayPair(x, y), ArrayPair(x[:64], y[:64]), net_map, class_num)

    def _run_cell(per_round, defense, codec, kernels):
        cfg = dict(
            dataset="synthetic_blobs", model="lr",
            client_num_in_total=pool, client_num_per_round=int(per_round),
            comm_round=warmup_rounds + measured_rounds,
            learning_rate=0.1, epochs=1, batch_size=spc,
            frequency_of_the_test=10_000, random_seed=0,
            federated_optimizer="FedAvg",
            defense_type=defense, byzantine_n=2,
            sanitize_updates=True,
            agg_kernels=bool(kernels),
            # synchronous rounds keep every phase inside its own round so
            # the breakdown sums are exact (see cohort_sweep_bench)
            prefetch=False,
        )
        if codec != "none":
            cfg["comm_codec"] = codec
        args = fedml_tpu.init(config=cfg)
        sim, _ = build_simulator(args, fed_data=fed)
        # shape/dtype template for the wire-byte estimate — the live params
        # are donated into the round step, so snapshot before run()
        params = jax.tree_util.tree_map(
            lambda l: np.zeros(l.shape, l.dtype), sim.params)
        hist = sim.run(apply_fn=None, log_fn=None)
        recs = hist[warmup_rounds:]
        wall = sum(r["round_time"] for r in recs)
        acc, sums_ok = {}, True
        for r in recs:
            ps = r["phases"]
            sums_ok = sums_ok and math.isclose(
                sum(ps.values()), r["round_time"],
                rel_tol=1e-6, abs_tol=1e-9)
            for k, v in ps.items():
                acc[k] = acc.get(k, 0.0) + v
        return params, {
            "rounds_per_sec": round(measured_rounds / wall, 4) if wall else None,
            "phase_breakdown_s": {
                k: round(v / measured_rounds, 6) for k, v in sorted(acc.items())},
            "phase_sum_equals_round_time": bool(sums_ok),
        }

    results = []
    for per_round in cohorts:
        for defense in defenses:
            for codec in codecs:
                params, unfused = _run_cell(per_round, defense, codec, False)
                _, fused = _run_cell(per_round, defense, codec, True)
                raw_pc, coded_pc = (
                    spec_wire_nbytes(codec, params) if codec != "none"
                    else ((lambda b: (b, b))(sum(
                        np.asarray(l).nbytes
                        for l in jax.tree_util.tree_leaves(params)))))
                ru, rf = unfused["rounds_per_sec"], fused["rounds_per_sec"]
                cell = {
                    "cohort": int(per_round), "defense": defense,
                    "codec": codec,
                    "wire_bytes_per_round": int(coded_pc) * int(per_round),
                    "raw_bytes_per_round": int(raw_pc) * int(per_round),
                    "unfused": unfused, "fused": fused,
                    "speedup_fused_over_unfused": (
                        round(rf / ru, 3) if ru and rf else None),
                }
                results.append(cell)
                print(f"agg-sweep: cohort={per_round} defense={defense} "
                      f"codec={codec} unfused={ru} fused={rf} r/s",
                      file=sys.stderr, flush=True)

    # --- double-buffered state movement: residual gather+scatter under the
    # prefetch pipeline vs the unoverlapped synchronous cost (SCAFFOLD so
    # every round moves real per-client arena state)
    state_cohort = min(1000, pool)

    def _state_run(prefetch, rounds=10):
        args = fedml_tpu.init(config=dict(
            dataset="synthetic_blobs", model="lr",
            client_num_in_total=pool, client_num_per_round=state_cohort,
            comm_round=rounds, learning_rate=0.1, epochs=1, batch_size=spc,
            frequency_of_the_test=10_000, random_seed=0,
            federated_optimizer="SCAFFOLD", prefetch=bool(prefetch),
            # full-pool capacity isolates the overlap mechanism from the
            # eviction policy: under capacity pressure put_take protect-
            # aborts (by design) and the run degenerates to the sync path
            client_state_capacity=pool,
        ))
        sim, _ = build_simulator(args, fed_data=fed)
        hist = sim.run(apply_fn=None, log_fn=None)
        # Window: skip the compile-heavy first rounds AND the last TWO
        # records — the final round has no successor so it scatters
        # synchronously, and under the deferred-readback attribution that
        # scatter lands in the second-to-last record. Per-phase MEDIAN, not
        # mean: a peek-miss round falls back to the sync scatter, and its
        # first use mid-run pays a one-time compile spike that would
        # otherwise dominate a short window; the gate is about the
        # recurring steady-state residual.
        recs = hist[3:-2]
        keys = {k for r in recs for k in r["phases"]}
        med = {}
        for k in keys:
            vals = sorted(r["phases"].get(k, 0.0) for r in recs)
            med[k] = vals[len(vals) // 2] if vals else 0.0
        engaged = sum(1 for r in recs if r["phases"].get("state_move", 0.0) > 0)
        return med, engaged, len(recs)

    sync_ph, _, _ = _state_run(False)
    pipe_ph, engaged_rounds, window_rounds = _state_run(True)
    unoverlapped = sync_ph.get("state_gather", 0.0) \
        + sync_ph.get("state_scatter", 0.0)
    residual = pipe_ph.get("state_gather", 0.0) \
        + pipe_ph.get("state_scatter", 0.0)
    ratio = (residual / unoverlapped) if unoverlapped > 0 else None
    overlap_pass = (ratio is not None and ratio <= 0.20
                    and engaged_rounds > 0)
    state_move = {
        "cohort": state_cohort,
        "unoverlapped_gather_scatter_s": round(unoverlapped, 6),
        "overlapped_residual_s": round(residual, 6),
        "state_move_s": round(pipe_ph.get("state_move", 0.0), 6),
        "engaged_rounds": f"{engaged_rounds}/{window_rounds}",
        "residual_ratio": round(ratio, 4) if ratio is not None else None,
        "pass_le_20pct": bool(overlap_pass),
    }

    backend = jax.default_backend()
    target = next((c for c in results
                   if c["cohort"] == 10000 and c["defense"] == "krum"
                   and c["codec"] == "q4"), None)
    speedup = (target or {}).get("speedup_fused_over_unfused")
    speedup_pass = speedup is not None and speedup >= 2.0
    all_sums = all(c["unfused"]["phase_sum_equals_round_time"]
                   and c["fused"]["phase_sum_equals_round_time"]
                   for c in results)
    line = {
        "metric": "agg_sweep_robust_frontier",
        "unit": (f"rounds/sec per (defense, codec, cohort) cell, FedAvg lr "
                 f"on synthetic blobs ({pool}-client pool, {spc} samples x "
                 f"dim {dim}), sanitizer on, agg_kernels off vs on, "
                 f"sync rounds; state-move block: SCAFFOLD cohort 1000, "
                 f"prefetch off vs on"),
        "backend": backend,
        "results": results,
        "state_move_overlap": state_move,
        "speedup_10k_krum_q4": speedup,
        "pass_10k_krum_q4_2x": bool(speedup_pass),
        "phase_sums_exact": bool(all_sums),
    }
    print(json.dumps(line), flush=True)
    # the 2x gate is a TPU gate: on CPU the Pallas kernels deliberately
    # fall back to the bit-identical jnp references (interpret mode exists
    # for parity testing, not speed), so fused == unfused arithmetic there
    ok = all_sums and overlap_pass and (speedup_pass or backend != "tpu")
    print(f"agg-sweep: phase_sums_exact={all_sums} "
          f"overlap_ratio={state_move['residual_ratio']} "
          f"(pass<=20%={overlap_pass}) 10k-krum-q4-speedup={speedup} "
          f"(backend={backend}) {'OK' if ok else 'BELOW TARGET'}",
          file=sys.stderr, flush=True)
    return 0 if ok else 1


def round_scan_bench(cohorts=(1000, 10000), scan_rs=(1, 2, 8, 32),
                     pool: int = 12000, measured_blocks: int = 2,
                     out_path: str = "BENCH_r09.json") -> int:
    """``--round-scan``: compiled multi-round dispatch sweep — rounds/sec
    per (cohort, rounds_per_dispatch) cell on the BENCH_r07 10k workload
    (FedAvg lr on synthetic blobs, sanitizer on, krum cell config), with
    the exact per-phase attribution asserted per round. The R=1 cell runs
    the classic per-round engine with prefetch off — the same protocol
    BENCH_r07's 6.92 r/s sync krum/none baseline used — so the speedup
    column is like for like.

    Two findings ride in the JSON: ``glue_s_per_round`` (pack_wait +
    scan_pack + host_other, the host-orchestration cost the scan
    amortizes — ~137 ms/round in BENCH_r07, sub-millisecond at R>=8) and
    a note that r07's ~50 us ``device`` phase was an async-dispatch
    measurement artifact: with the host glue gone, the round's genuine
    XLA compute (local-training GEMMs + gather + sanitize) is exposed as
    the new floor, so single-core CPU speedup saturates well below the
    glue-amortization factor."""
    import math

    import numpy as np

    import jax
    import fedml_tpu
    from fedml_tpu.data.federated import ArrayPair, build_federated_data
    from fedml_tpu.simulation import build_simulator

    spc, dim, class_num = 8, 64, 2
    rng = np.random.default_rng(0)
    n = pool * spc
    y = (np.arange(n) % class_num).astype(np.int64)
    x = rng.normal(size=(n, dim)).astype(np.float32) \
        + 2.0 * y[:, None].astype(np.float32)
    net_map = {c: list(range(c * spc, (c + 1) * spc)) for c in range(pool)}
    fed = build_federated_data(
        ArrayPair(x, y), ArrayPair(x[:64], y[:64]), net_map, class_num)

    def _run_cell(per_round, scan_r):
        # no apply_fn and an out-of-range eval frequency → no hook cuts, so
        # the plan is pure R-blocks; a round count that is an exact multiple
        # of R avoids a short tail block (which would compile a second
        # program inside the measured window). Skip the first block — it
        # carries the one compile — and measure the steady-state blocks.
        warmup = scan_r
        rounds = scan_r * (1 + measured_blocks)
        args = fedml_tpu.init(config=dict(
            dataset="synthetic_blobs", model="lr",
            client_num_in_total=pool, client_num_per_round=int(per_round),
            comm_round=rounds, learning_rate=0.1, epochs=1, batch_size=spc,
            frequency_of_the_test=10_000, random_seed=0,
            federated_optimizer="FedAvg",
            defense_type="krum", byzantine_n=2,
            sanitize_updates=True,
            rounds_per_dispatch=int(scan_r),
            # R=1 replays BENCH_r07's sync protocol exactly; fused blocks
            # run with the block prefetcher engaged (its intended mode)
            prefetch=scan_r > 1,
        ))
        sim, _ = build_simulator(args, fed_data=fed)
        hist = sim.run(apply_fn=None, log_fn=None)
        recs = hist[warmup:]
        wall = sum(r["round_time"] for r in recs)
        acc, sums_ok = {}, True
        for r in recs:
            ps = r["phases"]
            sums_ok = sums_ok and math.isclose(
                sum(ps.values()), r["round_time"],
                rel_tol=1e-6, abs_tol=1e-9)
            for k, v in ps.items():
                acc[k] = acc.get(k, 0.0) + v
        per = {k: v / len(recs) for k, v in acc.items()}
        glue = per.get("pack_wait", 0.0) + per.get("scan_pack", 0.0) \
            + per.get("host_other", 0.0)
        return {
            "cohort": int(per_round),
            "rounds_per_dispatch": int(scan_r),
            "measured_rounds": len(recs),
            "rounds_per_sec": round(len(recs) / wall, 4) if wall else None,
            "glue_s_per_round": round(glue, 6),
            "phase_breakdown_s": {k: round(v, 6)
                                  for k, v in sorted(per.items())},
            "phase_sum_equals_round_time": bool(sums_ok),
        }

    try:
        with open("BENCH_r07.json") as f:
            r07 = json.load(f)
        base = next(c["unfused"]["rounds_per_sec"] for c in r07["results"]
                    if c["cohort"] == 10000 and c["defense"] == "krum"
                    and c["codec"] == "none")
    except Exception:  # noqa: BLE001 — missing artifact must not kill the run
        base = None

    results = []
    for per_round in cohorts:
        for scan_r in scan_rs:
            cell = _run_cell(per_round, scan_r)
            results.append(cell)
            print(f"round-scan: cohort={per_round} R={scan_r} "
                  f"{cell['rounds_per_sec']} r/s "
                  f"glue={cell['glue_s_per_round'] * 1e3:.2f} ms/round "
                  f"sums_exact={cell['phase_sum_equals_round_time']}",
                  file=sys.stderr, flush=True)

    all_sums = all(c["phase_sum_equals_round_time"] for c in results)
    best_10k = max((c["rounds_per_sec"] or 0.0) for c in results
                   if c["cohort"] == 10000 and c["rounds_per_dispatch"] >= 8)
    speedup = round(best_10k / base, 3) if base else None
    r1_10k = next((c for c in results if c["cohort"] == 10000
                   and c["rounds_per_dispatch"] == 1), None)
    line = {
        "metric": "round_scan_dispatch",
        "unit": (f"rounds/sec per (cohort, rounds_per_dispatch) cell, "
                 f"FedAvg lr on synthetic blobs ({pool}-client pool, "
                 f"{spc} samples x dim {dim}), sanitizer on, BENCH_r07 "
                 f"krum/none cell protocol; R=1 sync prefetch-off"),
        "backend": jax.default_backend(),
        "results": results,
        "baseline_r07_10k_rounds_per_sec": base,
        "speedup_10k_scan_vs_r07": speedup,
        "glue_amortized_10k_s": (r1_10k or {}).get("glue_s_per_round"),
        "phase_sums_exact": bool(all_sums),
        "note": ("BENCH_r07's ~50us 'device' phase was an async-dispatch "
                 "artifact: XLA round compute hid inside pack_wait's "
                 "timeslices. With packing device-side and host glue "
                 "amortized over the block, the genuine per-round XLA "
                 "compute (local-update GEMMs + data gather + sanitize) "
                 "is the exposed floor, so rounds/sec saturates at that "
                 "floor on a single-core CPU host."),
    }
    print(json.dumps(line), flush=True)
    try:
        with open(out_path, "w") as f:
            json.dump(line, f, indent=1)
            f.write("\n")
    except OSError as e:
        print(f"round-scan: could not write {out_path}: {e}",
              file=sys.stderr, flush=True)
    print(f"round-scan: phase_sums_exact={all_sums} "
          f"best_10k_scan={best_10k} r/s vs r07 {base} "
          f"(speedup={speedup}) -> {out_path}",
          file=sys.stderr, flush=True)
    return 0 if all_sums else 1


def model_sweep_bench(model_axes=(1, 2, 4), rounds: int = 3) -> int:
    """``--model-sweep``: CPU-only memory-scaling sweep of the 2-D federated
    mesh — the same SCAFFOLD mnist/lr round loop on a fixed client axis (2)
    while the model axis grows 1 → 2 → 4. Per mesh it reports the per-device
    peak HBM from ``device.memory_stats()`` when the backend provides it
    (TPU), falling back to the per-device RESIDENT bytes of the persistent
    round state (params + server opt-state + client-state arena + EF
    residuals, summed over ``addressable_shards``) on backends that return
    None (CPU). Gate: peak per-device footprint must scale ≈1/model_axis
    (within 25% — small replicated-fallback leaves dilute the ratio)."""
    import numpy as np

    import jax
    import fedml_tpu
    from fedml_tpu.parallel.mesh import (AXIS_CLIENT, AXIS_MODEL, MeshConfig,
                                         create_mesh)
    from fedml_tpu.simulation import build_simulator

    devs = jax.devices()
    results = []
    for m in model_axes:
        if 2 * m > len(devs):
            print(f"model-sweep: skipping model_axis={m} "
                  f"(needs {2 * m} devices, have {len(devs)})",
                  file=sys.stderr, flush=True)
            continue
        axes = ((AXIS_CLIENT, 2),)
        if m > 1:
            axes += ((AXIS_MODEL, m),)
        mesh = create_mesh(MeshConfig(axes=axes), devices=devs[:2 * m])
        args = fedml_tpu.init(config=dict(
            dataset="mnist", model="lr", debug_small_data=True,
            client_num_in_total=12, client_num_per_round=4,
            comm_round=rounds, learning_rate=0.1, epochs=1, batch_size=32,
            frequency_of_the_test=10_000, random_seed=0,
            federated_optimizer="SCAFFOLD", prefetch=False,
        ))
        sim, _ = build_simulator(args, mesh=mesh)
        t0 = time.perf_counter()
        sim.run(apply_fn=None, log_fn=None)
        wall = time.perf_counter() - t0
        # resident persistent state per device: every leaf the round loop
        # keeps alive between rounds, attributed to the device holding each
        # shard — this is the footprint the model axis divides
        trees = [sim.params, sim.server_state]
        if sim._arena is not None:
            trees.append(list(sim._arena._leaves))
        if sim._codec_arena is not None:
            trees.append(list(sim._codec_arena._leaves))
        resident = {}
        for leaf in jax.tree.leaves(trees):
            for shd in leaf.addressable_shards:
                key = str(shd.device)
                resident[key] = resident.get(key, 0) + int(shd.data.nbytes)
        peaks, source = {}, "memory_stats.peak_bytes_in_use"
        for d in mesh.devices.flat:
            try:
                stats = d.memory_stats() or {}
            except Exception:
                stats = {}
            pk = stats.get("peak_bytes_in_use")
            if pk is not None:
                peaks[str(d)] = int(pk)
        if not peaks:
            # CPU backend: memory_stats() is None — fall back to the
            # resident-state accounting so the sweep stays meaningful
            peaks, source = dict(resident), "resident_state_bytes"
        results.append({
            "model_axis": int(m),
            "devices": int(2 * m),
            "rounds_per_sec": round(rounds / wall, 4) if wall else None,
            "hbm_source": source,
            "peak_bytes_per_device": {k: peaks[k] for k in sorted(peaks)},
            "peak_bytes_max": int(max(peaks.values())),
            "resident_state_bytes_max": int(max(resident.values())),
        })
        print(f"model-sweep: model_axis={m} "
              f"peak_max={results[-1]['peak_bytes_max']}B "
              f"({source})", file=sys.stderr, flush=True)
    by_axis = {r["model_axis"]: r for r in results}
    base = by_axis.get(1)
    scaling_ok = base is not None
    for r in results:
        if base is None or r["model_axis"] == 1:
            continue
        want = base["resident_state_bytes_max"] / r["model_axis"]
        got = r["resident_state_bytes_max"]
        r["scaling_vs_model_axis_1"] = round(
            base["resident_state_bytes_max"] / got, 3) if got else None
        if not (got <= want * 1.25):
            scaling_ok = False
    line = {
        "metric": "model_sweep_peak_hbm_bytes",
        "unit": ("peak per-device bytes vs model-axis size (client axis 2, "
                 "SCAFFOLD mnist/lr, arena client-state backend; hbm_source "
                 "says whether the backend reported memory_stats or the "
                 "resident-state fallback was used)"),
        "results": results,
        "pass_scales_inverse_model_axis": bool(scaling_ok),
    }
    print(json.dumps(line), flush=True)
    print(f"model-sweep: inverse-scaling={'OK' if scaling_ok else 'FAIL'}",
          file=sys.stderr, flush=True)
    return 0 if scaling_ok else 1


def chaos_bench(seed: int = 7) -> int:
    """``--chaos``: CPU-only robustness gate — a full loopback cross-silo
    deployment under a seeded fault plan (message drops + injected transient
    send failures + one client crash) must still complete every round. Same
    drill as ``fedml-tpu chaos-drill`` / tests/test_chaos.py; the JSON line
    reports rounds completed, wall time, and resilience-plane counters."""
    from fedml_tpu.cross_silo.chaos import run_chaos_drill

    result = run_chaos_drill(
        fault_seed=seed, fault_drop_rate=0.2, fault_fail_send_rate=0.2,
        fault_crash_rank=3, fault_crash_at_round=1,
    )
    line = {
        "metric": "chaos_drill_rounds_completed",
        "unit": (f"rounds closed under seeded faults (seed={seed}, drop 20%, "
                 "fail-send 20%, rank-3 crash at round 1) / rounds expected"),
        **result.json_record(),
    }
    print(json.dumps(line), flush=True)
    print(result.summary(), file=sys.stderr, flush=True)
    if not result.ok:
        return 1

    # second scenario: byzantine NaN uploads against the self-healing plane —
    # the sanitizer must quarantine the corrupted silo every round and the
    # run must stay finite and close every round
    byz = run_chaos_drill(
        fault_seed=seed, fault_byzantine_kind="nan",
        fault_byzantine_ranks=[2], sanitize_updates=True,
        local_test_on_all_clients=True, fault_drop_rate=0.0,
    )
    last_loss = (byz.history[-1].get("local_train_loss")
                 if byz.history else None)
    finite = last_loss is not None and last_loss == last_loss  # not NaN
    byz_ok = byz.ok and byz.quarantined > 0 and finite
    line = {
        "metric": "chaos_byzantine_quarantined",
        "unit": (f"sanitizer quarantine hits under NaN uploads from rank 2 "
                 f"(seed={seed}); run must close finite"),
        **byz.json_record(),
        "final_local_train_loss": (round(last_loss, 4)
                                   if finite else "non-finite"),
    }
    print(json.dumps(line), flush=True)
    print(byz.summary(), file=sys.stderr, flush=True)
    if not byz_ok:
        return 1

    # third + fourth scenarios: the hierarchical-federation failure domain —
    # a leaf aggregator killed mid-generation (its shard rehydrates on a
    # survivor) and a root<->leaf partition that heals after one round
    # window. Both gate exactly-once commits and accuracy against the
    # fault-free single-process reference.
    from fedml_tpu.cross_silo.chaos import run_tier_drill

    rc = 0
    for scenario in ("leaf_crash", "partition"):
        tier = run_tier_drill(scenario=scenario, random_seed=seed)
        line = {
            "metric": f"chaos_tier_{scenario}",
            "unit": ("client updates committed exactly once under a "
                     f"{scenario.replace('_', ' ')} (seed={seed}); accuracy "
                     "gated against the fault-free reference"),
            **tier.json_record(),
        }
        print(json.dumps(line), flush=True)
        print(tier.summary(), file=sys.stderr, flush=True)
        if not tier.ok:
            rc = 1
    return rc


def codec_sweep_bench(specs=("q8", "delta|topk:0.05|q8", "delta|topk:0.01|q8"),
                      rounds: int = 6) -> int:
    """``--codec-sweep``: accuracy-vs-bytes frontier of the compressed
    update plane. Per spec: one clean (fault-free) loopback cross-silo run
    reports final accuracy plus uplink raw/wire bytes (``fedml_codec_*``
    counter deltas); then one simulator run with the strongest spec checks
    the codec cost is attributed as its own phase and the phase breakdown
    still sums to round_time. Gates: uplink wire bytes strictly drop along
    the spec list (each spec is a strictly stronger compressor) and the
    phase sums stay exact."""
    import math

    import fedml_tpu
    from fedml_tpu.core import telemetry
    from fedml_tpu.cross_silo.chaos import run_chaos_drill
    from fedml_tpu.simulation import SimulatorSingleProcess

    telemetry.configure(enabled=True)
    common = dict(comm_round=rounds, fault_drop_rate=0.0, fault_seed=0)

    def final_acc(history):
        for rec in reversed(history):
            if "test_acc" in rec:
                return float(rec["test_acc"])
        return None

    base = run_chaos_drill(**common)
    results = [{
        "spec": None,
        "final_test_acc": final_acc(base.history),
        "uplink_wire_bytes": None,  # uncompressed: wire == raw
        "uplink_ratio": 1.0,
    }]
    wire_seq = []
    for spec in specs:
        r = run_chaos_drill(comm_codec=spec, **common)
        if not (r.ok and r.codec_bytes_wire.get("uplink")):
            print(f"codec-sweep: FAIL — spec '{spec}' run did not close "
                  "cleanly or recorded no uplink codec traffic",
                  file=sys.stderr, flush=True)
            return 1
        wire = r.codec_bytes_wire["uplink"]
        wire_seq.append(wire)
        results.append({
            "spec": spec,
            "final_test_acc": final_acc(r.history),
            "uplink_raw_bytes": int(r.codec_bytes_raw["uplink"]),
            "uplink_wire_bytes": int(wire),
            "uplink_ratio": round(r.codec_ratio("uplink"), 2),
        })
        print(f"codec-sweep: spec={spec!r} "
              f"acc={results[-1]['final_test_acc']} "
              f"ratio={results[-1]['uplink_ratio']}x",
              file=sys.stderr, flush=True)
    # uncompressed bytes basis: encode's nbytes_in is exactly the tree the
    # uncompressed run ships, so every compressed run reports the same raw
    results[0]["uplink_wire_bytes"] = results[1]["uplink_raw_bytes"]
    monotonic = all(a > b for a, b in zip(wire_seq, wire_seq[1:]))

    # simulator leg: same codec applied inside the compiled round step must
    # surface as its own "codec" phase and keep the breakdown exact
    args = fedml_tpu.init(config=dict(
        dataset="mnist", model="lr", debug_small_data=True,
        client_num_in_total=3, client_num_per_round=3, comm_round=3,
        learning_rate=0.1, batch_size=8, frequency_of_the_test=10_000,
        random_seed=0, prefetch=False, comm_codec=specs[-1],
    ))
    sim = SimulatorSingleProcess(args)
    hist = sim.run()
    # NOTE: deferred metric readback can drain one round's codec stamp into
    # the neighboring record, so the codec phase is asserted on the run
    # total, while the sum-to-round_time identity must hold per round
    phase_ok = True
    codec_phase = 0.0
    for rec in hist:
        ps = rec.get("phases", {})
        codec_phase += ps.get("codec", 0.0)
        phase_ok = phase_ok and math.isclose(
            sum(ps.values()), rec["round_time"], rel_tol=1e-6, abs_tol=1e-9)
    phase_ok = phase_ok and codec_phase > 0.0

    line = {
        "metric": "codec_sweep_accuracy_vs_bytes",
        "unit": (f"final test accuracy vs uplink bytes per codec spec, "
                 f"{rounds}-round clean loopback cross-silo drill (mnist lr, "
                 "3 silos) + simulator phase-attribution leg, CPU"),
        "results": results,
        "wire_bytes_monotonic_drop": bool(monotonic),
        "sim_codec_phase_s_per_round": round(codec_phase / max(len(hist), 1), 6),
        "sim_phase_sums_exact": bool(phase_ok),
    }
    print(json.dumps(line), flush=True)
    ok = monotonic and phase_ok
    print(f"codec-sweep: monotonic_bytes={monotonic} "
          f"sim_phases_exact={phase_ok} {'OK' if ok else 'FAIL'}",
          file=sys.stderr, flush=True)
    return 0 if ok else 1


def async_sweep_bench(buffer_sizes=(1, 2, 4, None), skew: float = 10.0,
                      rounds: int = 6) -> int:
    """``--async-sweep``: the sync-vs-async frontier of buffered-async
    aggregation. Per buffer size K (None = full cohort, the lockstep
    fallback): one sync and one async run of the simulation engine over the
    SAME seeded heavy-tail delay plan (slowest client ``skew``× the
    fastest), comparing committed-update goodput on the shared virtual
    clock against the barrier's round rate, plus final accuracy.

    Gates: every buffered K (< cohort) must clear goodput >= 3x the sync
    round rate at final accuracy within 2% of sync; the K == cohort run
    must replay the sync engine bit-for-bit (params equality); and every
    async commit record's phase breakdown must sum exactly to its
    round_time (the ``commit`` phase is attributed, not leaked into
    host_other)."""
    import math

    import jax
    import numpy as np

    import fedml_tpu
    from fedml_tpu.cross_silo.chaos import STRAGGLER_DEFAULTS
    from fedml_tpu.simulation import build_simulator
    from fedml_tpu.simulation.async_engine import sync_virtual_seconds
    from fedml_tpu.comm.resilience import ClientDelayPlan

    cfg = dict(STRAGGLER_DEFAULTS, comm_round=rounds, async_delay_skew=skew)
    cohort = int(cfg["client_num_per_round"])
    plan = ClientDelayPlan(
        seed=int(cfg["random_seed"]), base_s=float(cfg["async_delay_base_s"]),
        skew=skew, jitter=float(cfg["async_delay_jitter"]))
    sync_vs = sync_virtual_seconds(
        plan, float(cfg["async_delay_base_s"]), range(cohort), rounds)
    sync_round_rate = rounds / sync_vs

    def _run(extra):
        args = fedml_tpu.init(config=dict(cfg, **extra))
        sim, apply_fn = build_simulator(args)
        history = sim.run(apply_fn, log_fn=None)
        return sim, history

    def _acc(history):
        accs = [r["test_acc"] for r in history if "test_acc" in r]
        return float(accs[-1]) if accs else float("nan")

    sync_sim, sync_hist = _run({"async_mode": False})
    sync_acc = _acc(sync_hist)

    results = []
    gates_ok = True
    phase_ok = True
    lockstep_exact = None
    for k in buffer_sizes:
        k_eff = cohort if k is None else int(k)
        sim, hist = _run({"async_mode": True, "async_buffer_size": k_eff})
        stats = sim.async_stats()
        acc = _acc(hist)
        ratio = (stats["goodput_updates_per_s"] / sync_round_rate
                 if sync_round_rate > 0 else 0.0)
        for rec in hist:
            if "phases" in rec and not math.isclose(
                    sum(rec["phases"].values()), rec["round_time"],
                    rel_tol=1e-6, abs_tol=1e-9):
                phase_ok = False
        row = {
            "buffer_size": k_eff,
            "lockstep": k_eff == cohort,
            "commits": int(stats["version"]),
            "committed_updates": int(stats["committed_updates"]),
            "shed_updates": int(stats["shed_updates"]),
            "virtual_time_s": round(stats["virtual_time_s"], 4),
            "goodput_updates_per_vs": round(
                stats["goodput_updates_per_s"], 4),
            "goodput_over_sync_round_rate": round(ratio, 3),
            "final_acc": round(acc, 6),
            "acc_delta_vs_sync": round(sync_acc - acc, 6),
            "staleness_max": max(
                (int(r.get("staleness_max", 0)) for r in hist), default=0),
        }
        if k_eff == cohort:
            eq = jax.tree.map(
                lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
                sync_sim.params, sim.params)
            lockstep_exact = all(jax.tree_util.tree_leaves(eq)) and all(
                s.get("test_acc") == a.get("test_acc")
                for s, a in zip(sync_hist, hist) if "test_acc" in s)
            row["bit_exact_vs_sync"] = bool(lockstep_exact)
        else:
            row_ok = ratio >= 3.0 and (sync_acc - acc) <= 0.02
            row["pass_goodput_and_acc"] = bool(row_ok)
            gates_ok = gates_ok and row_ok
        results.append(row)
        print(f"async-sweep: K={k_eff} ratio={ratio:.1f}x acc={acc:.4f} "
              f"(sync {sync_acc:.4f})", file=sys.stderr, flush=True)

    ok = gates_ok and phase_ok and bool(lockstep_exact)
    line = {
        "metric": "async_sweep_goodput_frontier",
        "unit": (f"committed-update goodput vs sync round rate on the shared "
                 f"virtual clock ({skew:g}x seeded speed skew, digits/lr "
                 f"homo, cohort {cohort}, {rounds} rounds), per async "
                 "buffer size; lockstep row replays the sync engine"),
        "backend": "cpu",
        "sync_rounds_per_vs": round(sync_round_rate, 4),
        "sync_final_acc": round(sync_acc, 6),
        "results": results,
        "pass_goodput_3x_within_2pct": bool(gates_ok),
        "pass_lockstep_bit_exact": bool(lockstep_exact),
        "pass_phase_sums_exact": bool(phase_ok),
    }
    print(json.dumps(line), flush=True)
    print(f"async-sweep: {'OK' if ok else 'FAIL'} (goodput={gates_ok} "
          f"lockstep={lockstep_exact} phases={phase_ok})",
          file=sys.stderr, flush=True)
    return 0 if ok else 1


def loadgen_bench(duration_s: float = 2.0, seed: int = 0) -> int:
    """``--loadgen``: overload gate for the tenancy control plane — the
    check-in load generator must sustain >=10k offered check-ins/sec through
    the real message codec against a bounded queue, with shedding visible in
    the per-tenant counters and the queue depth never passing its bound. The
    JSON line records the throughput/shed frontier."""
    from fedml_tpu.core import telemetry
    from fedml_tpu.cross_silo.loadgen import run_loadgen

    telemetry.configure(enabled=True)
    report = run_loadgen(duration_s=duration_s, producers=2,
                         queue_maxsize=512, tenants=2, churn=0.1, seed=seed)
    rate_ok = report.offered_rate >= 10_000.0
    shed_visible = (report.shed == 0
                    or sum(report.per_tenant_shed.values()) > 0)
    line = {
        "metric": "loadgen_checkins_per_sec",
        "unit": (f"offered device check-ins/sec over {duration_s:.0f}s "
                 f"(2 producers, 2 tenants, 10% seeded churn, seed={seed}, "
                 "512-deep bounded queue), real msgpack codec both ways, CPU"),
        **report.json_record(),
        "pass_10k_per_sec": bool(rate_ok),
        "shed_visible_in_telemetry": bool(shed_visible),
    }
    print(json.dumps(line), flush=True)
    print(report.summary(), file=sys.stderr, flush=True)
    return 0 if (report.ok and rate_ok and shed_visible) else 1


def device_day_bench(seed: int = 0, budget_mb: float = 1536.0) -> int:
    """``--device-day``: the cross-device fleet gate. One full simulated day
    over a 1M-client registry on CPU: seeded diurnal arrivals through the
    bounded admission edge, cohorts folded through the tier-plane fan-in,
    per-device optimizer state tiered device->host->disk by the client-state
    arena.

    Gates: >= 50k offered check-ins/s of wall time at the admission edge
    itself; peak-RSS growth under ``budget_mb`` (the arena's spill tier, not
    RAM, absorbs the fleet's state); the disk tier actually engaged; closed
    shed/drop accounting with zero ledger duplicates; and the whole day
    byte-identical across two runs (history and params digests)."""
    import dataclasses
    import resource
    import tempfile

    from fedml_tpu.core import telemetry
    from fedml_tpu.cross_device.device_day import (DeviceDayConfig,
                                                   run_device_day)

    telemetry.configure(enabled=True)
    spill_root = tempfile.mkdtemp(prefix="device_day_bench_")
    cfg = DeviceDayConfig(
        registry_size=1_000_000, day_s=86_400.0, tick_s=300.0,
        num_classes=4, cohort=128, queue_maxsize=8192, peak_rate=6.0,
        max_commits_per_tick=1, arena_capacity=2048, host_capacity=16384,
        spill_dir=os.path.join(spill_root, "run1"), seed=seed)
    os.makedirs(cfg.spill_dir, exist_ok=True)
    rss_before_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    r1 = run_device_day(cfg)
    rss_after_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    rss_delta_mb = max(0.0, (rss_after_kb - rss_before_kb) / 1024.0)
    spill_files = len(os.listdir(cfg.spill_dir))
    cfg2 = dataclasses.replace(
        cfg, spill_dir=os.path.join(spill_root, "run2"))
    os.makedirs(cfg2.spill_dir, exist_ok=True)
    r2 = run_device_day(cfg2)

    pass_rate = r1.offered_per_s >= 50_000.0
    pass_rss = rss_delta_mb <= float(budget_mb)
    pass_spill = (spill_files > 0
                  and r1.arena_resident <= cfg.arena_capacity)
    pass_deterministic = (r1.history_digest == r2.history_digest
                          and r1.params_digest == r2.params_digest)
    line = {
        "metric": "device_day",
        "unit": ("one simulated 86400s day over a 1,000,000-device registry "
                 f"(288 ticks, seeded diurnal arrivals, seed={seed}), "
                 "bounded admission edge + DRR, cohort=128 tier-plane "
                 "fan-in, arena spill device->host->disk, CPU"),
        **r1.json_record(),
        "rss_delta_mb": round(rss_delta_mb, 1),
        "rss_budget_mb": float(budget_mb),
        "spill_files": spill_files,
        "pass_50k_per_sec_at_edge": bool(pass_rate),
        "pass_rss_budget": bool(pass_rss),
        "pass_spill_engaged": bool(pass_spill),
        "pass_deterministic_day": bool(pass_deterministic),
    }
    print(json.dumps(line), flush=True)
    print(r1.summary(), file=sys.stderr, flush=True)
    print(f"rss delta {rss_delta_mb:.0f}MB (budget {budget_mb:.0f}MB), "
          f"{spill_files} spill files, deterministic="
          f"{pass_deterministic}", file=sys.stderr, flush=True)
    return 0 if (r1.ok and r2.ok and pass_rate and pass_rss and pass_spill
                 and pass_deterministic) else 1


def serve_bench(rounds: int = 30, producers: int = 2,
                target_rate: float = 40_000.0, seed: int = 0) -> int:
    """``--serve``: the train/serve overlap gate. A real simulator trains
    (mnist/lr, debug data, every round committing a version through the
    canary-gated serving plane) while producer threads hammer the inference
    server; the serving window opens at the FIRST published version and
    stays open through every hot-swap until training ends and the queue
    drains.

    Gates: >= 10k requests/s served on CPU while training commits
    underneath; zero admitted requests dropped; >= 5 hot-swaps observed;
    and — the BENCH_r07 artifact fix — the per-round phase sums (stamped
    with ``bench_sync_device_phase``, which blocks on the committed params
    before the completion timestamp) must re-add to the round_time sum
    within 2%, with the ``device`` and ``publish`` phases both attributed
    instead of leaking into host_other."""
    import threading

    import jax
    import numpy as np

    import fedml_tpu
    from fedml_tpu.core import telemetry
    from fedml_tpu.cross_silo.chaos import TIER_DEFAULTS
    from fedml_tpu.serving import (InferenceServer, ServeConfig,
                                   held_out_batches)
    from fedml_tpu.simulation import build_simulator

    telemetry.configure(enabled=True)
    base = {k: v for k, v in TIER_DEFAULTS.items()
            if not k.startswith(("hier_", "group_", "lease_"))}
    args = fedml_tpu.init(config=dict(
        base, comm_round=rounds, random_seed=seed, frequency_of_the_test=1,
        prefetch=False, bench_sync_device_phase=True, serve_enabled=True))
    sim, apply_fn = build_simulator(args)
    cfg = ServeConfig.from_args(args)

    # fixed-shape jitted predict: every batch pads to batch_max so the
    # serve path compiles ONCE and a drain chunk of any size reuses it
    jpred = jax.jit(lambda p, x: apply_fn(p, x, train=False))
    bm = int(cfg.batch_max)

    def predict(params, x):
        x = np.asarray(x)
        n = int(x.shape[0])
        if n == bm:
            return np.asarray(jpred(params, x))
        xp = np.zeros((bm,) + tuple(x.shape[1:]), x.dtype)
        xp[:n] = x
        return np.asarray(jpred(params, xp))[:n]

    test = sim.fed.test_data_global
    server = InferenceServer(
        predict, cfg,
        eval_batches=held_out_batches(test.x, test.y, cfg.canary))
    first_pub = threading.Event()

    def publish(version, params):
        status = server.publish(version, params)
        first_pub.set()
        return status

    sim.attach_publisher(publish)

    x_pool = np.asarray(test.x)
    stop = threading.Event()
    per_rate = float(target_rate) / max(1, int(producers))

    def produce(worker: int) -> None:
        t0 = time.perf_counter()
        i = 0
        n_pool = len(x_pool)
        while not stop.is_set():
            server.submit(x_pool[(worker + i) % n_pool],
                          request_id=(worker, i))
            i += 1
            if i % 64 == 0:
                ahead = i / per_rate - (time.perf_counter() - t0)
                if ahead > 0.001:
                    time.sleep(min(ahead, 0.05))

    trainer = threading.Thread(target=lambda: sim.run(apply_fn, log_fn=None),
                               daemon=True, name="serve-bench-train")
    server.start()
    trainer.start()
    first_pub.wait(timeout=120.0)
    threads = [threading.Thread(target=produce, args=(w,), daemon=True,
                                name=f"serve-bench-p{w}")
               for w in range(max(1, int(producers)))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    trainer.join()
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    server.stop(drain=True)
    elapsed = time.perf_counter() - t0

    st = server.stats()
    served_rate = st["served"] / elapsed if elapsed > 0 else 0.0
    dropped = st["admitted"] - st["served"]
    swaps = st["store"]["swaps"]

    # corrected phase attribution (satellite of the serving PR): with
    # bench_sync_device_phase the completion stamp waits on the committed
    # params, so device time stops leaking into host_other
    hist = sim.history
    phase_sums = {}
    for rec in hist:
        for k, v in (rec.get("phases") or {}).items():
            phase_sums[k] = phase_sums.get(k, 0.0) + float(v)
    round_time_sum = sum(float(r.get("round_time", 0.0)) for r in hist)
    phase_total = sum(phase_sums.values())
    phase_drift = (abs(phase_total - round_time_sum) / round_time_sum
                   if round_time_sum > 0 else 1.0)

    rate_ok = served_rate >= 10_000.0
    drop_ok = dropped == 0 and st["served"] == st["admitted"]
    swap_ok = swaps >= 5
    phase_ok = (phase_drift <= 0.02 and phase_sums.get("device", 0.0) > 0
                and phase_sums.get("publish", 0.0) > 0)
    ok = rate_ok and drop_ok and swap_ok and phase_ok

    line = {
        "metric": "serve_requests_per_sec_under_training",
        "unit": (f"inference requests/s served while {rounds} training "
                 f"rounds commit versions through the canary gate "
                 f"(mnist/lr debug data, {producers} producers, "
                 f"batch_max {bm}, seed={seed}), CPU"),
        "elapsed_s": round(elapsed, 4),
        "served": st["served"],
        "served_per_sec": round(served_rate, 1),
        "admitted": st["admitted"],
        "submitted": st["submitted"],
        "shed": st["submitted"] - st["admitted"],
        "dropped": dropped,
        "canary_served": st["canary_served"],
        "swaps": swaps,
        "rollbacks": st["store"]["rollbacks"],
        "versions_served": len(st["served_by_version"]),
        "max_queue_depth": st["queue"]["max_depth"],
        "queue_maxsize": st["queue"]["maxsize"],
        "phase_sums_s": {k: round(v, 4)
                         for k, v in sorted(phase_sums.items())},
        "round_time_sum_s": round(round_time_sum, 4),
        "phase_drift_fraction": round(phase_drift, 4),
        "pass_10k_per_sec": bool(rate_ok),
        "pass_zero_dropped": bool(drop_ok),
        "pass_5_hot_swaps": bool(swap_ok),
        "pass_phase_sums_within_2pct": bool(phase_ok),
        "ok": bool(ok),
    }
    print(json.dumps(line), flush=True)
    print(f"serve: {'OK' if ok else 'FAIL'} — {served_rate:,.0f} req/s, "
          f"{swaps} swaps, dropped {dropped}, phase drift "
          f"{phase_drift:.2%}", file=sys.stderr, flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    if "--host-pack" in sys.argv:
        # host-side measurement only — never wait on (or measure) the chip
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        sys.exit(host_pack_bench())
    if "--cohort-sweep" in sys.argv:
        # cohort-axis scaling measurement — host + CPU backend only
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        sys.exit(cohort_sweep_bench())
    if "--agg-sweep" in sys.argv:
        # robust-aggregation frontier — CPU backend (kernels engage on TPU;
        # CPU runs the bit-identical reference fallbacks)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        sys.exit(agg_sweep_bench())
    if "--model-sweep" in sys.argv:
        # model-axis memory scaling — CPU backend with virtual devices; the
        # flag must land before the first backend init to take effect
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        sys.exit(model_sweep_bench())
    if "--chaos" in sys.argv:
        # protocol-level drill — loopback only, never touches the chip
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        sys.exit(chaos_bench())
    if "--codec-sweep" in sys.argv:
        # compression frontier — loopback + CPU simulator only
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        sys.exit(codec_sweep_bench())
    if "--async-sweep" in sys.argv:
        # buffered-async frontier — simulation engine on the CPU backend,
        # goodput measured on the seeded virtual clock (deterministic)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        sys.exit(async_sweep_bench())
    if "--loadgen" in sys.argv:
        # check-in overload drill — host threads + codec only, no chip
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        sys.exit(loadgen_bench())
    if "--device-day" in sys.argv:
        # cross-device fleet day — registry + admission edge + arena spill
        # are all host-side; the fold math runs on the CPU backend
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        sys.exit(device_day_bench())
    if "--serve" in sys.argv:
        # train/serve overlap gate — CPU simulator + host serving threads
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        sys.exit(serve_bench())
    if "--round-scan" in sys.argv:
        # compiled multi-round dispatch frontier — CPU backend; exits
        # nonzero if any round's phase breakdown fails the exactness check
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        sys.exit(round_scan_bench())
    sys.exit(main())

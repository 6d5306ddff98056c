"""Client-workload → device scheduler.

Parity: reference ``core/schedule/scheduler.py:4`` — a branch-and-bound search
assigning heterogeneous client workloads to devices under per-device memory
constraints, minimizing the makespan (max per-device cost). Redesign: the
reference explores every feasible partial map recursively (exponential fan-out,
kept "DP" only by pruning); here the same objective is solved with the classic
LPT greedy + local-refinement, which is O(n log n), deterministic, and within
4/3 of optimal — and the assignment feeds a *static* schedule so the compiled
per-shard client loop (Parrot-TPU) keeps rectangular shapes.
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import numpy as np


def dp_schedule(
    workloads: Sequence[float],
    constraints: Sequence[float],
    memory: Sequence[float],
) -> Tuple[List[List[int]], np.ndarray]:
    """Assign workload i (cost workloads[i] * constraints[device]) to devices.

    Args:
      workloads: per-client relative cost (e.g. sample counts).
      constraints: per-device slowdown factor (1.0 = fastest device).
      memory: per-device cost capacity; assignment never exceeds it.

    Returns:
      (assignment, device_costs): ``assignment[d]`` = client indices on device
      d; ``device_costs[d]`` = accumulated cost. Raises if infeasible.
    """
    workloads = np.asarray(workloads, dtype=np.float64)
    constraints = np.asarray(constraints, dtype=np.float64)
    memory = np.asarray(memory, dtype=np.float64)
    n_dev = len(constraints)
    order = np.argsort(workloads)[::-1]  # longest processing time first
    assignment: List[List[int]] = [[] for _ in range(n_dev)]
    costs = np.zeros(n_dev)
    for i in order:
        # device that ends up with the smallest resulting makespan and fits
        cand_costs = costs + constraints * workloads[i]
        feasible = cand_costs <= memory
        if not feasible.any():
            raise ValueError(
                f"workload {int(i)} (cost {workloads[i]}) fits no device memory"
            )
        cand = np.where(feasible, cand_costs, np.inf)
        d = int(np.argmin(cand))
        assignment[d].append(int(i))
        costs[d] = cand_costs[d]
    # local refinement: move a job from the busiest device if it lowers makespan
    improved = True
    while improved:
        improved = False
        busiest = int(np.argmax(costs))
        for job in sorted(assignment[busiest], key=lambda j: workloads[j]):
            for d in np.argsort(costs):
                d = int(d)
                if d == busiest:
                    continue
                new_cost = costs[d] + constraints[d] * workloads[job]
                if new_cost < costs[busiest] and new_cost <= memory[d]:
                    assignment[busiest].remove(job)
                    assignment[d].append(job)
                    costs[busiest] -= constraints[busiest] * workloads[job]
                    costs[d] = new_cost
                    improved = True
                    break
            if improved:
                break
    return assignment, costs


def bucket_schedule(
    batch_counts: Sequence[int],
    axis: int,
    max_buckets: int = 4,
    max_width: int | None = None,
) -> List[Tuple[np.ndarray, int]]:
    """Group cohort positions into width-buckets minimizing padded compute.

    The compiled round step is rectangular: every client slot costs
    ``width`` batches regardless of its true batch count, and slot counts
    pad up to a multiple of the mesh client axis. Splitting a skewed cohort
    into a few width-classes (each compiled once — widths are cohort maxima,
    so at most ``max_buckets`` distinct shapes) trades a handful of extra
    XLA programs for dropping the padding waste.

    Exact dynamic program over the sorted counts (the honest successor of
    the reference's branch-and-bound ``DP_schedule``,
    ``core/schedule/scheduler.py:110``): cost of a contiguous sorted group
    = padded_slots(group) * width(group); minimize the total over at most
    ``max_buckets`` groups. Widths are rounded UP to powers of two so the
    per-(slots, width) compiled programs converge to a handful of shapes
    across rounds with varying cohorts instead of recompiling every round.

    Returns: list of (positions, width) — positions index into
    ``batch_counts``; widths ascending powers of two.

    Pure in its arguments, and on the per-round host hot path (the async
    cohort pipeline rebuilds the schedule every round): results are
    memoized on the (counts, axis, max_buckets, max_width) key, with
    defensive copies returned so callers can never corrupt the cache.
    """
    cached = _bucket_schedule_cached(
        tuple(int(c) for c in batch_counts), int(axis), int(max_buckets),
        None if max_width is None else int(max_width))
    return [(pos.copy(), w) for pos, w in cached]


@functools.lru_cache(maxsize=64)
def _bucket_schedule_cached(
    batch_counts: Tuple[int, ...],
    axis: int,
    max_buckets: int,
    max_width: int | None,
) -> List[Tuple[np.ndarray, int]]:
    counts = np.asarray(batch_counts, dtype=np.int64)
    n = len(counts)
    axis = max(1, int(axis))
    if n == 0:
        return []
    order = np.argsort(counts, kind="stable")
    # quantize each client's width requirement up to a power of two; the DP
    # then groups on the quantized ladder (a group's width = its max).
    # max_width caps the ladder (callers pass their per-client batch cap so
    # quantization never raises a client's effective training budget).
    sc = 1 << np.ceil(np.log2(np.maximum(counts[order], 1))).astype(np.int64)
    if max_width is not None:
        sc = np.minimum(sc, int(max_width))

    B = max(1, min(int(max_buckets), n))
    INF = np.inf
    # f[b][j] = min cost of first j sorted clients using <= b groups;
    # inner minimization vectorized over the split point i (this runs on the
    # per-round hot path, so no O(n^2) pure-Python loops)
    i_idx = np.arange(n)  # candidate split starts
    f_prev = np.full(n + 1, INF)
    f_prev[0] = 0.0
    back = np.zeros((B + 1, n + 1), dtype=np.int64)
    for b in range(1, B + 1):
        f_cur = np.full(n + 1, INF)
        f_cur[0] = 0.0
        for j in range(1, n + 1):
            # group [i, j) at width sc[j-1]; slot count mirrors execution:
            # ceil(k/axis) rounded UP to a power of two, times axis
            k = j - i_idx[:j]
            per_axis = -(-k // axis)
            per_axis = (2 ** np.ceil(np.log2(np.maximum(per_axis, 1)))).astype(np.int64)
            cand = f_prev[:j] + per_axis * axis * int(sc[j - 1])
            arg = int(np.argmin(cand))
            f_cur[j] = cand[arg]
            back[b][j] = arg
        f_prev = f_cur
    # reconstruct
    cuts = []
    j, b = n, B
    while j > 0:
        i = int(back[b][j])
        cuts.append((i, j))
        j, b = i, b - 1
    cuts.reverse()
    return [
        (order[i:j].astype(np.int64), int(sc[j - 1])) for i, j in cuts if j > i
    ]


def lane_schedule(
    batch_counts: Sequence[int],
    axis: int,
    max_lanes: int | None = None,
    force_lanes: int | None = None,
) -> Tuple[List[List[int]], int]:
    """Pack cohort positions into G balanced lanes for the packed executor.

    The packed cohort schedule trains clients BACK-TO-BACK inside one
    compiled scan (param reset at client boundaries), so the only padding is
    the lane-length imbalance: cost = G * L where L = max lane load. This
    searches G over multiples of ``axis`` (lanes shard over the mesh client
    axis), assigns clients to lanes with LPT (longest-processing-time
    greedy), and keeps the (G, L) minimizing total padded batch-work —
    ties broken toward MORE lanes (fatter per-step batches, fewer
    sequential steps).

    Returns: (lanes, L) — lanes[g] is the ordered list of cohort positions
    lane g trains; L = max lane length in batches.

    Memoized like ``bucket_schedule`` (pure, per-round hot path); lane
    lists are copied on the way out so callers can't corrupt the cache.
    """
    lanes, L = _lane_schedule_cached(
        tuple(int(c) for c in batch_counts), int(axis),
        None if max_lanes is None else int(max_lanes),
        None if force_lanes is None else int(force_lanes))
    return [list(lane) for lane in lanes], L


@functools.lru_cache(maxsize=64)
def _lane_schedule_cached(
    batch_counts: Tuple[int, ...],
    axis: int,
    max_lanes: int | None,
    force_lanes: int | None,
) -> Tuple[List[List[int]], int]:
    counts = np.asarray(batch_counts, dtype=np.int64)
    n = len(counts)
    axis = max(1, int(axis))
    cap = n if max_lanes is None else min(n, int(max_lanes))
    order = np.argsort(-counts, kind="stable")  # LPT: biggest first
    best = None
    # candidate lane counts: axis * powers of two only — every distinct G
    # is a fresh vmap width and therefore a full XLA recompile of the
    # training scan, so the candidate set must stay tiny as cohorts
    # resample round to round (the bucketed schedule bounds its shapes the
    # same way with pow2 slot counts)
    candidates = []
    if force_lanes is not None:
        # caller pins G (SimConfig.packed_lanes says why one would); still a
        # multiple of the mesh axis — both the round-up and the cohort
        # clamp floor to axis multiples so mesh shards stay even
        g = max(axis, -(-int(force_lanes) // axis) * axis)
        g = min(g, max(axis, (cap // axis) * axis))
        if g <= cap:
            candidates.append(g)
        # g > cap (cohort smaller than one axis-multiple) falls through to
        # the n < axis pad fallback below
    else:
        g = axis
        while g <= cap:
            candidates.append(g)
            g *= 2
    for g in candidates:
        loads = np.zeros(g, dtype=np.int64)
        lanes: List[List[int]] = [[] for _ in range(g)]
        for pos in order:
            lane = int(np.argmin(loads))
            lanes[lane].append(int(pos))
            loads[lane] += counts[pos]
        L = int(loads.max())
        cost = g * L
        # ties -> larger g (checked last wins on <=)
        if best is None or cost <= best[0]:
            best = (cost, lanes, L)
    if best is None:  # n < axis: one client per lane, pad lanes to axis
        lanes = [[int(p)] for p in order] + [[] for _ in range(axis - n)]
        return lanes, int(counts.max(initial=1))
    return best[1], best[2]


def even_client_schedule(client_indexes: Sequence[int], n_shards: int) -> List[np.ndarray]:
    """np.array_split semantics of the reference NCCL simulator's
    ``client_schedule`` (``nccl/base_framework/Server.py:109``): contiguous
    even split of the sampled cohort across mesh shards."""
    return list(np.array_split(np.asarray(client_indexes, dtype=np.int32), n_shards))


def balanced_client_schedule(
    client_indexes: Sequence[int],
    sample_counts: Sequence[int],
    n_shards: int,
) -> List[np.ndarray]:
    """Workload-aware split: LPT-balance sampled clients across shards by
    sample count (what the reference's commented-out scheduler integration,
    ``Server.py:113-120``, intended), then pad shards to equal length by
    repeating the last client so shapes stay rectangular for the compiled
    per-shard scan — repeated entries get zero aggregation weight upstream."""
    counts = np.asarray([sample_counts[i] for i in client_indexes], dtype=np.float64)
    assignment, _ = dp_schedule(counts, np.ones(n_shards), np.full(n_shards, np.inf))
    shards = [np.asarray([client_indexes[j] for j in a], dtype=np.int32) for a in assignment]
    width = max(1, max(len(s) for s in shards))
    return [
        np.pad(s, (0, width - len(s)), mode="edge") if len(s) else
        np.full(width, client_indexes[0], np.int32)
        for s in shards
    ]

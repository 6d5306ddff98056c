"""Runner ``fed_round``: the program's own simulator, built by
``build_simulator`` from the configuration and driven through
``FedSimulator.run``, rounds back to back.

Set-up makes the data and the weights of ``reference/fed.py`` from the seed,
hands them to the simulator, and runs the first ``check_steps`` rounds, which
compile and give the readings that decide ``correct``; the same simulator then
runs the window. ``run`` has no clock of its own: the window gives it more
rounds than fit and ends it from the simulator's per-round hook
(``_round_gate``) once the time is up, then waits for the last round
dispatched. Every ``run`` starts again at round 0 with the model carried on,
so the window replays the cohorts of rounds 0, 1, 2, ... after the check's."""

from __future__ import annotations

import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from reference import fed as ref


# faults planted in the reference put in the program's place (limits.py)
FAULTS = {"half_cohort": {"drop_half_cohort": True}}


class _WindowClosed(Exception):
    pass


def to_program(w: dict) -> dict:
    """The reference's weights in ``ResNet18``'s tree."""
    norm = lambda s, b: {"scale": s, "bias": b}  # noqa: E731
    tree = {"Conv_0": {"kernel": w["stem"]},
            "Dense_0": {"kernel": w["fc_w"], "bias": w["fc_b"]}}
    for i, b in enumerate(w["blocks"]):
        blk = {"Conv_0": {"kernel": b["c1"]}, "GroupNorm_0": norm(b["g1s"], b["g1b"]),
               "Conv_1": {"kernel": b["c2"]}, "GroupNorm_1": norm(b["g2s"], b["g2b"])}
        if "pc" in b:
            blk["proj"] = {"kernel": b["pc"]}
            blk["proj_norm"] = norm(b["pgs"], b["pgb"])
        tree[f"BasicBlock_{i}"] = blk
    return {"params": tree}


def from_program(tree: dict) -> dict:
    p = tree["params"]
    blocks = []
    for i in range(8):
        b = p[f"BasicBlock_{i}"]
        out = {"c1": b["Conv_0"]["kernel"], "c2": b["Conv_1"]["kernel"],
               "g1s": b["GroupNorm_0"]["scale"], "g1b": b["GroupNorm_0"]["bias"],
               "g2s": b["GroupNorm_1"]["scale"], "g2b": b["GroupNorm_1"]["bias"]}
        if "proj" in b:
            out.update(pc=b["proj"]["kernel"], pgs=b["proj_norm"]["scale"],
                       pgb=b["proj_norm"]["bias"])
        blocks.append(out)
    return {"stem": p["Conv_0"]["kernel"], "blocks": blocks,
            "fc_w": p["Dense_0"]["kernel"], "fc_b": p["Dense_0"]["bias"]}


@jax.jit
def _change(tree, start):
    return jax.tree.map(jnp.subtract, tree, start)


def build(ctx):
    """The program's simulator for this configuration and traffic, holding
    the reference's seeded data; ``build_simulator`` is the program's own
    entry."""
    from fedml_tpu.data.federated import ArrayPair, build_federated_data
    from fedml_tpu.simulation import build_simulator

    cfg, traffic = ctx.config, ctx.traffic
    x, y, index = ref.make_data(ctx.seed, cfg)
    fed = build_federated_data(
        ArrayPair(x, y), ArrayPair(x[:200], y[:200]), index,
        cfg["num_classes"])
    args = types.SimpleNamespace(
        dataset=cfg["dataset"], model=cfg["model"],
        partition_method=cfg["partition_method"],
        client_num_in_total=cfg["client_num_in_total"],
        client_num_per_round=traffic["client_num_per_round"],
        batch_size=cfg["batch_size"], epochs=cfg["epochs"],
        client_optimizer=cfg["client_optimizer"],
        learning_rate=cfg["learning_rate"],
        federated_optimizer=cfg["federated_optimizer"],
        use_bf16=cfg["compute_dtype"] == "bfloat16",
        random_seed=ctx.seed, comm_round=traffic["check_steps"],
        frequency_of_the_test=10 ** 9)
    sim, _ = build_simulator(args, fed_data=fed)
    return sim


class Run:
    def __init__(self, ctx):
        cfg, traffic = ctx.config, ctx.traffic
        self.ctx = ctx
        self.sim = build(ctx)
        self.samples_per_round = (traffic["client_num_per_round"]
                                  * cfg["examples_per_client"])
        self.reset(ctx.seed)
        self.readings = self.check_rounds(traffic["check_steps"])

    def reset(self, seed: int) -> None:
        weights = to_program(ref.init_weights(seed, self.ctx.config))
        if jax.tree.structure(weights) != jax.tree.structure(self.sim.params):
            raise RuntimeError("ResNet18's parameter tree has changed: "
                               "runners/fed_round.py no longer maps onto it")
        self.sim.params = weights

    def _run(self, rounds: int, gate) -> list:
        """``FedSimulator.run`` over ``rounds`` rounds with ``gate`` as the
        per-round hook; the records it added to the history."""
        sim = self.sim
        sim.cfg.comm_round = rounds
        sim._round_gate = gate
        before = len(sim.history)
        try:
            with self.ctx.span("fed_run"):
                sim.run(apply_fn=None, log_fn=None)
        except _WindowClosed:
            pass
        return sim.history[before:]

    def check_rounds(self, rounds: int) -> dict:
        """The first rounds from the seed, through the window's own call."""
        start = jax.tree.map(jnp.copy, self.sim.params)
        first = {}

        def gate(round_idx):
            if round_idx == 1:  # round 0 is dispatched: its aggregate update
                first["delta"] = _change(self.sim.params, start)

        recs = self._run(rounds, gate)
        if "delta" not in first:  # a single check round
            first["delta"] = _change(self.sim.params, start)
        return {
            "loss": [float(r["train_loss"]) for r in recs],
            "grad1": ref.leaf_norms(from_program(first["delta"])),
            "change": ref.leaf_norms(from_program(
                _change(self.sim.params, start))),
        }

    def window(self, seconds: float, tick) -> dict:
        sim = self.sim
        t_start = time.perf_counter()
        deadline = t_start + seconds
        dispatched = 0

        def gate(round_idx):
            nonlocal dispatched
            now = time.perf_counter()
            tick(now - t_start)
            if now >= deadline:
                raise _WindowClosed
            dispatched += 1

        recs = []
        while time.perf_counter() < deadline:
            recs += self._run(self.ctx.traffic["max_rounds"], gate)
        # the round dispatched last has no record: the hook ended ``run``
        # before its metrics were read. Wait for it here.
        jax.block_until_ready(sim.params)
        wall = time.perf_counter() - t_start
        times = [r["round_time"] for r in recs]
        if dispatched > len(recs):
            times.append(wall - sum(times))
        failed = sum(not np.isfinite(r["train_loss"]) for r in recs)
        phases = {}
        for r in recs:
            for name, dt in r["phases"].items():
                phases.setdefault(name, []).append(dt)
        return {"step_s": times, "wall_s": wall, "rate_name": "rounds_per_s",
                "units": len(times),
                "flop_units": len(times) * self.samples_per_round,
                "attempted": dispatched, "failed": failed, "phases": phases}

    def close(self) -> None:
        self.sim = None


def reference(ctx, **kw) -> dict:
    return ref.readings(ctx.seed, ctx.config, ctx.traffic, **kw)

"""Runner ``lm_step``: the program's own ``DistributedLMTrainer`` on one chip,
driven step after step through ``trainer.step`` (which ends in the host's read
of the loss).

Set-up builds the trainer, puts the seeded weights of ``reference/lm.py`` in
it, and drives it through the first ``check_steps`` steps, which compile and
give the readings that decide ``correct``; the same object then runs the
window."""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from reference import lm as ref

ADAM_B1 = 0.9  # optax.adamw's default, which the trainer leaves alone
# faults planted in the reference put in the program's place (limits.py)
FAULTS = {"half_batch": {"drop_half_batch": True}}


@functools.partial(jax.jit, static_argnums=1)
def to_program(w: dict, layers: int) -> dict:
    """The reference's stacked weights in ``TransformerLM``'s tree."""
    tree = {
        "wte": {"embedding": w["wte"]}, "wpe": {"embedding": w["wpe"]},
        "ln_f": {"scale": w["lnf_g"], "bias": w["lnf_b"]},
        "head": {"kernel": w["head"]},
    }
    for i in range(layers):
        tree[f"block_{i}"] = {
            "LayerNorm_0": {"scale": w["ln1_g"][i], "bias": w["ln1_b"][i]},
            "SelfAttention_0": {"qkv": {"kernel": w["qkv"][i]},
                                "proj": {"kernel": w["proj"][i]}},
            "LayerNorm_1": {"scale": w["ln2_g"][i], "bias": w["ln2_b"][i]},
            "MLPBlock_0": {
                "Dense_0": {"kernel": w["fc_w"][i], "bias": w["fc_b"][i]},
                "Dense_1": {"kernel": w["out_w"][i], "bias": w["out_b"][i]}},
        }
    return {"params": tree}


def from_program(tree: dict, layers: int) -> dict:
    """Inverse of ``to_program`` (used on norms as well as on tensors)."""
    p = tree["params"]
    blocks = [p[f"block_{i}"] for i in range(layers)]
    stack = lambda f: jnp.stack([f(b) for b in blocks])  # noqa: E731
    return {
        "wte": p["wte"]["embedding"], "wpe": p["wpe"]["embedding"],
        "ln1_g": stack(lambda b: b["LayerNorm_0"]["scale"]),
        "ln1_b": stack(lambda b: b["LayerNorm_0"]["bias"]),
        "qkv": stack(lambda b: b["SelfAttention_0"]["qkv"]["kernel"]),
        "proj": stack(lambda b: b["SelfAttention_0"]["proj"]["kernel"]),
        "ln2_g": stack(lambda b: b["LayerNorm_1"]["scale"]),
        "ln2_b": stack(lambda b: b["LayerNorm_1"]["bias"]),
        "fc_w": stack(lambda b: b["MLPBlock_0"]["Dense_0"]["kernel"]),
        "fc_b": stack(lambda b: b["MLPBlock_0"]["Dense_0"]["bias"]),
        "out_w": stack(lambda b: b["MLPBlock_0"]["Dense_1"]["kernel"]),
        "out_b": stack(lambda b: b["MLPBlock_0"]["Dense_1"]["bias"]),
        "lnf_g": p["ln_f"]["scale"], "lnf_b": p["ln_f"]["bias"],
        "head": p["head"]["kernel"],
    }


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(a.astype(jnp.float32) ** 2)), tree)


@jax.jit
def _change_norms(tree, start):
    return _norms(jax.tree.map(jnp.subtract, tree, start))


def _named(norm_tree: dict, layers: int) -> dict:
    """Program-tree of scalar norms -> the reference's leaf names."""
    flat = from_program(norm_tree, layers)
    out = {}
    for name, a in flat.items():
        a = np.asarray(a)
        if a.ndim:
            out.update({f"{name}/{i}": float(x) for i, x in enumerate(a)})
        else:
            out[name] = float(a)
    return out


class Run:
    def __init__(self, ctx):
        from fedml_tpu.parallel.trainer import (
            DistributedLMTrainer,
            DistTrainConfig,
        )

        cfg, traffic = ctx.config, ctx.traffic
        o = cfg["optimizer"]
        self.ctx, self.layers = ctx, cfg["n_layer"]
        self.trainer = DistributedLMTrainer(
            DistTrainConfig(dp=1, tp=1, sp=1, lr=o["lr"],
                            weight_decay=o["weight_decay"], use_remat=True,
                            remat_policy=cfg["remat"]),
            vocab_size=cfg["vocab_size"], dim=cfg["n_embd"],
            num_heads=cfg["n_head"], num_layers=cfg["n_layer"],
            max_len=cfg["n_positions"], dtype=jnp.dtype(cfg["compute_dtype"]),
            seed=0)
        self.batches = ref.make_batches(ctx.seed, cfg, traffic)
        self.tokens_per_step = traffic["batch"] * traffic["seq_len"]
        self.cursor = 0
        self.reset(ctx.seed)
        self.readings = self.check_steps(traffic["check_steps"])

    def reset(self, seed: int) -> None:
        """Seeded weights into the trainer, fresh AdamW moments."""
        t = self.trainer
        weights = to_program(ref.init_weights(seed, self.ctx.config),
                             self.layers)
        if jax.tree.structure(weights) != jax.tree.structure(t.params):
            raise RuntimeError("TransformerLM's parameter tree has changed: "
                               "runners/lm_step.py no longer maps onto it")
        t.params = t.opt_state = None
        t.params = jax.device_put(weights, t.param_shardings)
        t.opt_state = t.opt.init(t.params)

    def _step(self) -> float:
        b = self.batches[self.cursor % len(self.batches)]
        self.cursor += 1
        with self.ctx.span("lm_step"):
            return self.trainer.step(b[:, :-1], b[:, 1:])

    def check_steps(self, steps: int) -> dict:
        """The first steps from the seed, through the window's own call."""
        losses, grad1 = [], None
        for i in range(steps):
            losses.append(self._step())
            if i == 0:
                mu = self.trainer.opt_state[0].mu
                grad1 = {k: v / (1 - ADAM_B1) for k, v in
                         _named(_norms(mu), self.layers).items()}
        start = to_program(ref.init_weights(self.ctx.seed, self.ctx.config),
                           self.layers)
        change = _named(_change_norms(self.trainer.params, start), self.layers)
        return {"loss": losses, "grad1": grad1, "change": change}

    def window(self, seconds: float, tick) -> dict:
        times, failed = [], 0
        t_start = last = time.perf_counter()
        deadline = t_start + seconds
        while last < deadline:
            loss = self._step()
            now = time.perf_counter()
            times.append(now - last)
            failed += not np.isfinite(loss)
            last = now
            tick(now - t_start)  # a no-op unless this run is traced
        return {"step_s": times, "wall_s": last - t_start,
                "rate_name": "tokens_per_s",
                "units": self.tokens_per_step * len(times),
                "flop_units": self.tokens_per_step * len(times),
                "attempted": len(times), "failed": failed, "phases": {}}

    def close(self) -> None:
        self.trainer.params = self.trainer.opt_state = None
        self.trainer = None


def reference(ctx, **kw) -> dict:
    return ref.readings(ctx.seed, ctx.config, ctx.traffic, **kw)

"""Runner ``lfm2_step``: the program's own ``DistributedLMTrainer`` on one
chip, holding the ``layer_types`` decoder that the configuration describes
(a chip's share of LFM2-24B-A2B), driven step after step through
``trainer.step``: the path ``runners/lm_step.py`` drives for the GPT-2 cell.

Set-up builds the trainer from the configuration, puts the seeded weights
and the balanced selection biases of ``reference/lfm2.py`` in it (the biases
stay as set-up leaves them), and drives it through the first ``check_steps``
steps, which compile and give the readings that decide ``correct``; the same
object then runs the window."""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from reference import lfm2 as ref
from runners import lm_step

# faults planted in the reference put in the program's place (limits.py)
FAULTS = {"half_batch": {"drop_half_batch": True},
          "capacity_drop": {"capacity_drop": True}}
# the reference's leaf (after ``L<i>.``) -> its path inside the layer's tree
LAYER_PATHS = {
    "op_norm": ("operator_norm", "scale"), "ffn_norm": ("ffn_norm", "scale"),
    "conv_in": ("conv", "in_proj", "kernel"), "conv_taps": ("conv", "taps"),
    "conv_out": ("conv", "out_proj", "kernel"),
    "wq": ("attn", "q_proj", "kernel"), "wk": ("attn", "k_proj", "kernel"),
    "wv": ("attn", "v_proj", "kernel"), "wo": ("attn", "o_proj", "kernel"),
    "q_norm": ("attn", "q_norm", "scale"), "k_norm": ("attn", "k_norm", "scale"),
    "w1": ("mlp", "w1", "kernel"), "w3": ("mlp", "w3", "kernel"),
    "w2": ("mlp", "w2", "kernel"), "gate": ("moe", "gate"),
    "ew1": ("moe", "w1"), "ew3": ("moe", "w3"), "ew2": ("moe", "w2"),
    "expert_bias": ("moe", "expert_bias")}
TOP_PATHS = {"embed": ("embed", "embedding"), "final_norm": ("final_norm", "scale")}


def _path(name: str) -> tuple:
    if name in TOP_PATHS:
        return TOP_PATHS[name]
    layer, leaf = name.split(".")
    return ("layer_" + layer[1:],) + LAYER_PATHS[leaf]


def to_program(flat: dict) -> dict:
    """The reference's flat ``name -> leaf`` as the decoder's nested tree."""
    tree: dict = {}
    for name, leaf in flat.items():
        *parents, last = _path(name)
        node = tree
        for key in parents:
            node = node.setdefault(key, {})
        node[last] = leaf
    return tree


def from_program(tree: dict, names) -> dict:
    """Inverse of ``to_program`` over ``names`` (on norms as on tensors)."""
    out = {}
    for name in names:
        node = tree
        for key in _path(name):
            node = node[key]
        out[name] = node
    return out


def decoder_config(cfg: dict):
    from fedml_tpu.models.hybrid_lm import DecoderConfig

    if not (cfg["norm_topk_prob"] and cfg["use_expert_bias"]
            and cfg["routed_scaling_factor"] == 1 and not cfg["conv_bias"]):
        raise ValueError("the program's routed experts normalise their top-k "
                         "weights, scale by 1 and choose by a selection bias, "
                         "and its convolution has no bias: the configuration "
                         "asks for something else")
    return DecoderConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_width"],
        experts_held=(cfg["experts_held_offset"], cfg["num_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim"), conv_L_cache=cfg["conv_L_cache"],
        norm_eps=cfg["norm_eps"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]))


@jax.jit
def _norms(flat: dict) -> dict:
    """Each leaf's l2 norm; an expert tensor's per expert, as the reference's
    ``leaf_norms`` gives them."""
    def norm(name, a):
        a = a.astype(jnp.float32) ** 2
        if name.rsplit(".", 1)[-1] in ref.EXPERT_LEAVES:
            return jnp.sqrt(jnp.sum(a, axis=(1, 2)))
        return jnp.sqrt(jnp.sum(a))
    return {name: norm(name, a) for name, a in flat.items()}


def _named(norms: dict) -> dict:
    out = {}
    for name, a in norms.items():
        a = np.asarray(a)
        if a.ndim:
            out.update({f"{name}/{e}": float(x) for e, x in enumerate(a)})
        else:
            out[name] = float(a)
    return out


class Run(lm_step.Run):
    """``lm_step.Run``'s step and window over another trainer and tree."""

    def __init__(self, ctx):
        from fedml_tpu.parallel.trainer import (
            DistributedLMTrainer,
            DistTrainConfig,
        )

        cfg, traffic = ctx.config, ctx.traffic
        o = cfg["optimizer"]
        self.ctx = ctx
        self.trainer = DistributedLMTrainer(
            DistTrainConfig(dp=1, tp=1, sp=1, lr=o["lr"],
                            weight_decay=o["weight_decay"], use_remat=True,
                            remat_policy=cfg["remat"],
                            warmup_steps=o["warmup_steps"]),
            dtype=jnp.dtype(cfg["compute_dtype"]), seed=0,
            model=decoder_config(cfg))
        self.batches = ref.make_batches(ctx.seed, cfg, traffic)
        self.tokens_per_step = traffic["batch"] * traffic["seq_len"]
        self.cursor = 0
        self.names = self.reset(ctx.seed)
        self.readings = self.check_steps(traffic["check_steps"])

    def reset(self, seed: int) -> list:
        """Seeded weights and selection biases into the trainer, fresh AdamW
        moments. Returns the reference's leaf names."""
        t = self.trainer
        weights, biases = ref.seeded(seed, self.ctx.config, self.ctx.traffic)
        params = {"params": to_program(weights)}
        buffers = {"buffers": to_program(biases)}
        if (jax.tree.structure(params) != jax.tree.structure(t.params)
                or jax.tree.structure(buffers) != jax.tree.structure(t.constants)):
            raise RuntimeError("HybridLM's tree has changed: "
                               "runners/lfm2_step.py no longer maps onto it")
        t.params = t.opt_state = None
        t.params = jax.device_put(params, t.param_shardings)
        t.constants.update(jax.device_put(
            buffers, jax.tree.map(lambda a: a.sharding, t.constants)))
        t.opt_state = t.init_opt_state()
        return list(weights)

    def _flat(self, tree: dict) -> dict:
        return from_program(tree["params"], self.names)

    def check_steps(self, steps: int) -> dict:
        """The first steps from the seed, through the window's own call."""
        losses, grad1 = [], None
        for i in range(steps):
            losses.append(self._step())
            if i == 0:
                mu = self._flat(self.trainer.opt_state[0].mu)
                grad1 = {k: v / (1 - lm_step.ADAM_B1)
                         for k, v in _named(_norms(mu)).items()}
        start = ref.init_weights(self.ctx.seed, self.ctx.config)[0]
        now = self._flat(self.trainer.params)
        change = _named(_norms(jax.tree.map(jnp.subtract, now, start)))
        return {"loss": losses, "grad1": grad1, "change": change}

    def close(self) -> None:
        from fedml_tpu.core.telemetry import get_registry

        counters = get_registry().snapshot()["counters"]
        print("routing: " + ", ".join(
            f"{k} = {v:.0f}" for k, v in sorted(counters.items())
            if k.startswith("fedml_moe_")), file=sys.stderr, flush=True)
        self.trainer.constants = None
        super().close()


def reference(ctx, **kw) -> dict:
    return ref.readings(ctx.seed, ctx.config, ctx.traffic, **kw)

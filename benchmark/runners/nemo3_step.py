"""Runner ``nemo3_step``: the program's own ``DistributedLMTrainer`` on one
chip, holding the one-mixer-a-block decoder that the configuration describes
(a chip's share of NVIDIA-Nemotron-3-Nano-30B-A3B: Mamba-2, attention and
expert blocks), driven step after step through ``trainer.step``: the path
``runners/lm_step.py`` and ``runners/lfm2_step.py`` drive for their cells.

Set-up builds the trainer from the configuration, puts the seeded weights
and the balanced selection biases of ``reference/nemotron_h.py`` in it (the
biases stay as set-up leaves them), and drives it through the first
``check_steps`` steps, which compile and give the readings that decide
``correct``; the same object then runs the window."""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from reference import nemotron_h as ref
from runners import lm_step
from runners.lfm2_step import _named  # norms by the reference's leaf names

# faults planted in the reference put in the program's place (limits.py)
FAULTS = {"half_batch": {"drop_half_batch": True},
          "capacity_drop": {"capacity_drop": True},
          "state_reset": {"state_reset": True}}
# the reference's leaf (after ``L<i>.``) -> its path inside the block's tree
BLOCK_PATHS = {
    "norm": ("norm", "scale"),
    "in_proj": ("mamba", "in_proj", "kernel"), "conv_w": ("mamba", "conv_weight"),
    "conv_b": ("mamba", "conv_bias"), "dt_bias": ("mamba", "dt_bias"),
    "A_log": ("mamba", "A_log"), "D": ("mamba", "D"), "gnorm": ("mamba", "norm"),
    "out_proj": ("mamba", "out_proj", "kernel"),
    "wq": ("attn", "q_proj", "kernel"), "wk": ("attn", "k_proj", "kernel"),
    "wv": ("attn", "v_proj", "kernel"), "wo": ("attn", "o_proj", "kernel"),
    "gate": ("moe", "gate"), "ew1": ("moe", "w1"), "ew2": ("moe", "w2"),
    "sw1": ("moe", "shared_w1"), "sw2": ("moe", "shared_w2"),
    "expert_bias": ("moe", "expert_bias")}
TOP_PATHS = {"embed": ("embed", "embedding"), "final_norm": ("final_norm", "scale"),
             "head": ("lm_head",)}


def _path(name: str) -> tuple:
    if name in TOP_PATHS:
        return TOP_PATHS[name]
    block, leaf = name.split(".")
    return ("layer_" + block[1:],) + BLOCK_PATHS[leaf]


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
    from fedml_tpu.models.hybrid_lm import DecoderConfig, layer_types_of_pattern

    if not (cfg["norm_topk_prob"] and cfg["use_conv_bias"]
            and cfg["n_shared_experts"] == 1 and cfg["n_group"] == 1
            and cfg["topk_group"] == 1
            and not (cfg["use_bias"] or cfg["mlp_bias"] or cfg["attention_bias"]
                     or cfg["mamba_proj_bias"] or cfg["tie_word_embeddings"])
            and cfg["mamba_hidden_act"] == "silu"
            and cfg["norm_eps"] == cfg["layer_norm_epsilon"]):
        raise ValueError(
            "the program's one-mixer blocks have no bias in a projection, a "
            "bias in the convolution, SiLU in the Mamba mixer, one shared "
            "expert, normalised top-k weights with no group limit, one norm "
            "epsilon and an untied head: the configuration asks for "
            "something else")
    return DecoderConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=layer_types_of_pattern(cfg["hybrid_override_pattern"]),
        num_dense_layers=0, intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_width"],
        experts_held=(cfg["experts_held_offset"], cfg["n_routed_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], norm_eps=cfg["layer_norm_epsilon"],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        time_step_min=cfg["time_step_min"], time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        moe_shared_expert_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        mlp_hidden_act=cfg["mlp_hidden_act"],
        tie_word_embeddings=cfg["tie_word_embeddings"])


@jax.jit
def _norms(flat: dict, start=None) -> dict:
    """Each leaf's l2 norm, or with ``start`` the norm of its change from
    there (in the one program, so that no difference is kept whole); an
    expert tensor's per expert, as the reference's ``leaf_norms`` gives
    them."""
    def norm(name, a):
        a = a.astype(jnp.float32)
        a = (a if start is None else a - start[name]) ** 2
        if name.rsplit(".", 1)[-1] in ref.EXPERT_LEAVES:
            return jnp.sqrt(jnp.sum(a, axis=(1, 2)))
        return jnp.sqrt(jnp.sum(a))
    return {name: norm(name, a) for name, a in flat.items()}


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
        trees = jax.tree.structure(t.params), jax.tree.structure(t.constants)
        # the trainer's own start goes before the seeded one is made: beside
        # it the reference's weights and its balancing pass would put set-up's
        # peak above anything the window holds
        t.params = t.opt_state = None
        weights, biases = ref.seeded(seed, self.ctx.config, self.ctx.traffic)
        params = {"params": to_program(weights)}
        buffers = {"buffers": to_program(biases)}
        if (jax.tree.structure(params), jax.tree.structure(buffers)) != trees:
            raise RuntimeError("HybridLM's tree has changed: "
                               "runners/nemo3_step.py no longer maps onto it")
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
        change = _named(_norms(self._flat(self.trainer.params), start))
        return {"loss": losses, "grad1": grad1, "change": change}

    def close(self) -> None:
        from fedml_tpu.core.telemetry import get_registry

        counters = get_registry().snapshot()["counters"]
        print("routing and scan: " + ", ".join(
            f"{k} = {v:.0f}" for k, v in sorted(counters.items())
            if k.startswith(("fedml_moe_", "fedml_ssd_"))),
            file=sys.stderr, flush=True)
        self.trainer.constants = None
        super().close()


def reference(ctx, **kw) -> dict:
    return ref.readings(ctx.seed, ctx.config, ctx.traffic, **kw)

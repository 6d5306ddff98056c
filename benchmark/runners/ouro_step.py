"""Runner ``ouro_step``: the program's own ``DistributedLMTrainer`` on one
chip, holding the looped decoder that the configuration describes (one
pipeline stage's layers of Ouro-2.6B, applied ``total_ut_steps`` times over
one set of weights, with the exit gate and the objective over the passes),
driven step after step through ``trainer.step``: the path
``runners/lm_step.py``, ``lfm2_step.py`` and ``nemo3_step.py`` drive for
their cells.

Set-up builds the trainer from the configuration, puts the seeded weights of
``reference/ouro.py`` in it, and drives it through the first ``check_steps``
steps, which compile and give the readings that decide ``correct``; the same
object then runs the window."""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from reference import ouro as ref
from runners import lm_step
from runners.lfm2_step import _named  # norms by the reference's leaf names

# faults planted in the reference put in the program's place (limits.py)
FAULTS = {"half_batch": {"drop_half_batch": True},
          "pass_dropped": {"pass_dropped": True},
          "last_pass_only": {"last_pass_only": True},
          "gate_stopped": {"gate_stopped": True}}
# the reference's leaf (after ``L<i>.``) -> its path inside the layer's tree
LAYER_PATHS = {
    "n1": ("operator_norm", "scale"), "n2": ("operator_out_norm", "scale"),
    "n3": ("ffn_norm", "scale"), "n4": ("ffn_out_norm", "scale"),
    "wq": ("attn", "q_proj", "kernel"), "wk": ("attn", "k_proj", "kernel"),
    "wv": ("attn", "v_proj", "kernel"), "wo": ("attn", "o_proj", "kernel"),
    "w1": ("mlp", "w1", "kernel"), "w3": ("mlp", "w3", "kernel"),
    "w2": ("mlp", "w2", "kernel")}
TOP_PATHS = {"embed": ("embed", "embedding"), "final_norm": ("final_norm", "scale"),
             "head": ("lm_head",), "gate_w": ("exit_gate", "kernel"),
             "gate_b": ("exit_gate", "bias")}


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

    ref.shape_of(cfg)  # refuses what the reference does not state either
    return DecoderConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=len(cfg["layer_types"]),  # every layer's FF is dense
        intermediate_size=cfg["intermediate_size"], moe_intermediate_size=0,
        num_experts=0, num_experts_per_tok=0,
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        tie_word_embeddings=cfg["tie_word_embeddings"], qk_norm=False,
        sandwich_norm=True, total_ut_steps=cfg["total_ut_steps"])


@jax.jit
def _norms(flat: dict, start=None) -> dict:
    """Each leaf's l2 norm, or with ``start`` the norm of its change from
    there (in the one program, so that no difference is kept whole)."""
    def norm(name, a):
        a = a.astype(jnp.float32)
        return jnp.sqrt(jnp.sum((a if start is None else a - start[name]) ** 2))
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
                            warmup_steps=o["warmup_steps"],
                            exit_entropy_weight=cfg["exit_entropy_beta"]),
            dtype=jnp.dtype(cfg["compute_dtype"]), seed=0,
            model=decoder_config(cfg))
        self.batches = ref.make_batches(ctx.seed, cfg, traffic)
        self.tokens_per_step = traffic["batch"] * traffic["seq_len"]
        self.cursor = 0
        self.names = self.reset(ctx.seed)
        self.readings = self.check_steps(traffic["check_steps"])

    def reset(self, seed: int) -> list:
        """Seeded weights into the trainer, fresh AdamW moments. Returns the
        reference's leaf names."""
        t = self.trainer
        structure = jax.tree.structure(t.params)
        # the trainer's own start goes before the seeded one is made: both at
        # once, with their moments, would be set-up's peak and not the window's
        t.params = t.opt_state = None
        weights = ref.init_weights(seed, self.ctx.config)
        params = {"params": to_program(weights)}
        if jax.tree.structure(params) != structure:
            raise RuntimeError("HybridLM's tree has changed: "
                               "runners/ouro_step.py no longer maps onto it")
        t.params = jax.device_put(params, t.param_shardings)
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
        start = ref.init_weights(self.ctx.seed, self.ctx.config)
        change = _named(_norms(self._flat(self.trainer.params), start))
        return {"loss": losses, "grad1": grad1, "change": change}

    def close(self) -> None:
        from fedml_tpu.core.telemetry import get_registry

        snap = get_registry().snapshot()
        said = {**snap["counters"], **snap["gauges"]}
        print("passes and exits: " + ", ".join(
            f"{k} = {v:.6g}" for k, v in sorted(said.items())
            if k.startswith(("fedml_lm_ut_", "fedml_lm_exit_"))),
            file=sys.stderr, flush=True)
        super().close()


def reference(ctx, **kw) -> dict:
    return ref.readings(ctx.seed, ctx.config, ctx.traffic, **kw)

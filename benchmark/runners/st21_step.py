"""Runner ``st21_step``: the program's own ``DistributedLMTrainer`` on one
chip, holding the decoder that the configuration describes (a chip's share
of SmallThinker-21B-A3B: one global attention layer with no position
embedding and three rotary layers over a sliding window, each over routed
ReGLU experts with a softmax router placed before attention), driven step
after step through ``trainer.step``: the path ``runners/lm_step.py``,
``lfm2_step.py``, ``nemo3_step.py`` and ``ouro_step.py`` drive for their
cells.

Set-up builds the trainer from the configuration, puts the seeded weights of
``reference/smallthinker.py`` in it, and drives it through the first
``check_steps`` steps, which compile and give the readings that decide
``correct``; the same object then runs the window."""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from reference import smallthinker as ref
from runners import lm_step
from runners.lfm2_step import _named  # norms by the reference's leaf names

# faults planted in the reference put in the program's place (limits.py)
FAULTS = {"half_batch": {"drop_half_batch": True},
          "no_window": {"no_window": True},
          "late_router": {"late_router": True}}
# the reference's leaf (after ``L<i>.``) -> its path inside the layer's tree
LAYER_PATHS = {
    "n1": ("operator_norm", "scale"), "n2": ("ffn_norm", "scale"),
    "wq": ("attn", "q_proj", "kernel"), "wk": ("attn", "k_proj", "kernel"),
    "wv": ("attn", "v_proj", "kernel"), "wo": ("attn", "o_proj", "kernel"),
    "gate": ("moe", "gate"), "ew1": ("moe", "w1"), "ew3": ("moe", "w3"),
    "ew2": ("moe", "w2")}
TOP_PATHS = {"embed": ("embed", "embedding"), "final_norm": ("final_norm", "scale"),
             "head": ("lm_head",)}


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
    layers = cfg["num_hidden_layers"]
    return DecoderConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=("full_attention",) * layers,
        num_dense_layers=0, intermediate_size=0,  # every layer's FF routes
        moe_intermediate_size=cfg["moe_ffn_hidden_size"],
        num_experts=cfg["router_width"],
        experts_held=(cfg["experts_held_offset"],
                      cfg["moe_num_primary_experts"]),
        num_experts_per_tok=cfg["moe_num_active_primary_experts"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        mlp_hidden_act=cfg["mlp_hidden_act"],
        tie_word_embeddings=cfg["tie_word_embeddings"], qk_norm=False,
        sliding_window_size=cfg["sliding_window_size"],
        sliding_window_layout=tuple(cfg["sliding_window_layout"]),
        rope_layout=tuple(cfg["rope_layout"]),
        moe_primary_router_apply_softmax=cfg[
            "moe_primary_router_apply_softmax"],
        early_router=cfg["early_router"])


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
                               "runners/st21_step.py no longer maps onto it")
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

        counters = get_registry().snapshot()["counters"]
        print("routing and bands: " + ", ".join(
            f"{k} = {v:.0f}" for k, v in sorted(counters.items())
            if k.startswith(("fedml_moe_", "fedml_flash_window_"))),
            file=sys.stderr, flush=True)
        super().close()


def reference(ctx, **kw) -> dict:
    return ref.readings(ctx.seed, ctx.config, ctx.traffic, **kw)

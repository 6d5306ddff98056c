"""Cheetah: distributed training acceleration (dp × tp × sp over one mesh).

The reference reserves this product line as an empty placeholder
(``python/fedml/distributed/`` — SURVEY.md product table: "Cheetah ...
placeholder only"); here it is functional. A causal-LM training step is jit
over a ``(data, seq, model)`` mesh:

- **data**: batch sharding; XLA inserts the gradient psum (the DDP
  equivalent, reference ``trainer_dist_adapter.py:66-68``).
- **model**: tensor parallelism via parameter PartitionSpecs — column-sharded
  qkv/mlp-in kernels, row-sharded proj/mlp-out, vocab-sharded head; GSPMD
  places the activation collectives (Megatron layout, expressed as shardings
  not hand-written collectives, per the scaling-book recipe).
- **seq**: sequence/context parallelism — tokens sharded along T; attention
  runs as explicit ring attention (``ops/attention.py``) with K/V blocks
  rotating on ``ppermute`` over ICI. This is the long-context axis
  (SURVEY.md §5.7: absent in reference, first-class here).

Pipeline (``pipe``) is intentionally not in this trainer: at FL/LM scales the
same devices are better spent on dp×tp×sp; SplitNN (algorithms/split_nn.py)
covers the layer-split execution pattern.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.telemetry import get_registry, get_tracer, install_jax_collectors
from ..models.hybrid_lm import DecoderConfig, HybridLM
from ..models.transformer import TransformerLM
from ..ops.losses import (
    chunked_lm_cross_entropy,
    expected_exit_loss,
    lm_cross_entropy,
)
from .mesh import AXIS_DATA, AXIS_MODEL, AXIS_SEQ, MeshConfig, create_mesh
from .sharding import replicated_specs, transformer_param_specs, tree_shardings

PyTree = Any


@dataclasses.dataclass(frozen=True)
class DistTrainConfig:
    dp: int = 1
    tp: int = 1
    sp: int = 1
    lr: float = 3e-4
    weight_decay: float = 0.01
    use_remat: bool = True   # jax.checkpoint the blocks: FLOPs for HBM
    # "full" recomputes the whole block in bwd; "dots" saves matmul
    # outputs and recomputes only elementwise/norm ops — most of full
    # remat's memory win at a fraction of its recompute FLOPs
    # (models/transformer.py remat; the two are not compared on this chip)
    remat_policy: str = "full"
    # chunked LM cross-entropy (ops/losses.chunked_lm_cross_entropy):
    # never materializes the (B, T, V) f32 logits — the large-vocab HBM
    # hog. 0 disables; otherwise the sequence-chunk size.
    ce_chunk: int = 0
    # sequence-parallel collective pattern: "ring" (ppermute blockwise,
    # O(T/sp) memory) or "ulysses" (all-to-all seq<->heads re-shard,
    # full-sequence flash-eligible attention; heads % sp == 0)
    sp_impl: str = "ring"
    # AdamW first-moment dtype: "bfloat16" halves mu's HBM footprint and
    # the optimizer stage's read/write traffic (mu tolerates bf16; nu
    # stays f32 — bf16's 7-bit mantissa loses the small per-step squared
    # gradients against the accumulated sum, stalling the second moment).
    # Its effect on the step is not measured on this chip.
    mu_dtype: Optional[str] = None
    # linear learning-rate warm-up: step t (from 1) runs at lr * t /
    # warmup_steps until it reaches lr; 0 is a constant lr
    warmup_steps: int = 0
    # a looped decoder's objective (ops/losses.expected_exit_loss): the
    # weight of the exit distribution's entropy, taken from the expected loss
    exit_entropy_weight: float = 0.1


def make_lm_mesh(cfg: DistTrainConfig, devices=None) -> Mesh:
    """dp x sp x tp mesh over ``devices`` (default: the first dp*sp*tp of
    ``jax.devices()`` — a one-chip config on a four-chip host takes one)."""
    if devices is None:
        devices = jax.devices()[: cfg.dp * cfg.sp * cfg.tp]
    return create_mesh(
        MeshConfig(axes=((AXIS_DATA, cfg.dp), (AXIS_SEQ, cfg.sp), (AXIS_MODEL, cfg.tp))),
        devices=devices,
    )


# spec logic lives in the shared sharding layer (the federated simulator's
# 2-D mesh uses the same module); re-exported here for back-compat
__all__ = ["transformer_param_specs", "DistTrainConfig", "DistributedLMTrainer",
           "make_lm_mesh"]


class DistributedLMTrainer:
    """Compiled distributed causal-LM trainer (the Cheetah engine).

    ``model`` is the model's configuration: a ``DecoderConfig`` builds the
    ``layer_types`` decoder (models/hybrid_lm.py; dp only: its expert layer
    and its grouped KV heads have no tp or sp layout yet). Without it the
    five integers build the GPT-2-style ``TransformerLM``, as before.

    ``params`` holds what the optimizer trains (flax's ``params``
    collection); ``constants`` every other collection the model made, which
    each step reads and no step moves (a router's selection bias).

    What the trainer asks of a model beside ``init`` and ``apply``:
    ``head_kernel(params)``, the (D, V) output head (for ``ce_chunk`` and
    for a looped decoder's objective), and ``STEP_STATS``, the names of the
    statistics a step hands back in one vector with the loss: those
    ``apply(..., return_stats=True)`` gives beside its output (none: no such
    keyword), then those of a looped decoder's passes. The model's
    ``count_step_stats(registry, stats, dp)`` files them."""

    def __init__(
        self,
        cfg: DistTrainConfig,
        vocab_size: int = 1024,
        dim: int = 256,
        num_heads: int = 8,
        num_layers: int = 4,
        max_len: int = 2048,
        dtype=jnp.bfloat16,
        mesh: Optional[Mesh] = None,
        seed: int = 0,
        model: Optional[DecoderConfig] = None,
    ):
        # jax's compile phases become spans: the first step's trace,
        # lowering and compile are children of its ``lm.dispatch``
        install_jax_collectors()
        tracer = get_tracer()
        with tracer.span("lm.trainer_init"):
            self.cfg = cfg
            self.mesh = mesh or make_lm_mesh(cfg)
            # per-block remat: O(1) layers of activations alive in bwd —
            # strictly better than checkpointing the whole apply (which
            # still holds every layer alive during the recompute)
            remat = ((cfg.remat_policy if cfg.remat_policy != "full" else True)
                     if cfg.use_remat else False)
            if model is not None:
                if cfg.tp > 1 or cfg.sp > 1:
                    raise NotImplementedError(
                        "the layer_types decoder trains under dp only: its "
                        "routed experts and grouped KV heads have no tensor- "
                        f"or sequence-parallel layout (tp={cfg.tp}, sp={cfg.sp})")
                self.model = HybridLM(
                    model, dtype=dtype, mesh=self.mesh, remat=remat)
            else:
                self.model = TransformerLM(
                    vocab_size=vocab_size, dim=dim, num_heads=num_heads,
                    num_layers=num_layers, max_len=max_len, dtype=dtype,
                    seq_axis=AXIS_SEQ if cfg.sp > 1 else None,
                    mesh=self.mesh,
                    sp_impl=cfg.sp_impl,
                    remat=remat,
                )
            with tracer.span("lm.init_params"):
                # init on host with a tiny batch, then place with TP
                # shardings; the init token length must divide by sp (ring
                # attention shards T)
                # (the decoder's init as one program: op by op, its kernels
                # and sorts each compile alone)
                init = self.model.init if model is None else jax.jit(
                    self.model.init)
                variables = init(
                    jax.random.PRNGKey(seed),
                    jnp.zeros((1, 8 * max(1, cfg.sp)), jnp.int32)
                )
                specs = (transformer_param_specs if model is None
                         else replicated_specs)(variables)
                constants = {k: v for k, v in variables.items() if k != "params"}
                self.param_specs = {"params": specs["params"]}
                self.param_shardings = tree_shardings(self.mesh, self.param_specs)
                self.params = jax.device_put(
                    {"params": variables["params"]}, self.param_shardings)
                self.constants = jax.device_put(constants, tree_shardings(
                    self.mesh, {k: specs[k] for k in constants}))
            with tracer.span("lm.opt_init"):
                lr = cfg.lr
                if cfg.warmup_steps:
                    lr = lambda count: cfg.lr * jnp.minimum(  # noqa: E731
                        1.0, (count + 1) / cfg.warmup_steps)
                self.opt = optax.adamw(
                    lr, weight_decay=cfg.weight_decay,
                    mu_dtype=jnp.dtype(cfg.mu_dtype) if cfg.mu_dtype else None)
                self.opt_state = self.init_opt_state()
            self.batch_sharding = NamedSharding(self.mesh, P(AXIS_DATA, AXIS_SEQ))
            self.step_stats = self.model.STEP_STATS
            with tracer.span("lm.build_step"):
                self._train_step = self._build_train_step()

    def init_opt_state(self):
        """Fresh optimizer state for ``params``, placed as a step hands it
        back: the moments inherit the params' shardings (init maps over
        sharded params), the step count is committed to the mesh, so the
        first step's program is the second's too."""
        rep = NamedSharding(self.mesh, P())
        return jax.tree.map(
            lambda a: a if isinstance(a.sharding, NamedSharding)
            else jax.device_put(a, rep), self.opt.init(self.params))

    def _build_train_step(self) -> Callable:
        model = self.model
        opt = self.opt
        ce_chunk = self.cfg.ce_chunk
        beta = self.cfg.exit_entropy_weight
        looped = isinstance(model, HybridLM) and model.cfg.total_ut_steps > 1
        # a model that routes hands back, in one vector with the loss, the
        # statistics its STEP_STATS names
        stats_kw = {"return_stats": True} if self.step_stats else {}

        def loss_fn(params, constants, tokens, targets):
            # block-level remat is baked into the model (cfg.use_remat)
            # the scopes are metadata: they name the ops of the loss and of
            # the optimizer in a device trace, and change no arithmetic
            def apply(**kw):
                out = model.apply({**params, **constants}, tokens, **kw,
                                  **stats_kw)
                return out if stats_kw else (out, None)

            if looped:
                # every pass's hidden state and gate logit; the objective
                # runs the head once a pass and adds its own statistics
                (hid, gates), stats = apply(return_passes=True)
                head = model.head_kernel(params).astype(hid.dtype)
                loss, nll, mass = expected_exit_loss(hid, gates, head,
                                                     targets, beta)
                return loss, jnp.concatenate(
                    [stats.astype(jnp.float32), nll, mass])
            if ce_chunk:
                hid, stats = apply(return_hidden=True)
                with jax.named_scope("lm.loss"):
                    head = model.head_kernel(params).astype(hid.dtype)
                    return chunked_lm_cross_entropy(hid, head, targets,
                                                    chunk=ce_chunk), stats
            logits, stats = apply()
            with jax.named_scope("lm.loss"):
                return lm_cross_entropy(logits, targets), stats

        def train_step(params, opt_state, constants, tokens, targets):
            (loss, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, constants, tokens, targets)
            with jax.named_scope("lm.optimizer"):
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            if stats_kw:  # counts far under 2**24: exact in float32
                loss = jnp.concatenate([loss[None], stats.astype(jnp.float32)])
            return params, opt_state, loss

        rep = NamedSharding(self.mesh, P())
        return jax.jit(
            train_step,
            in_shardings=(self.param_shardings, None,
                          jax.tree.map(lambda a: a.sharding, self.constants),
                          self.batch_sharding, self.batch_sharding),
            out_shardings=(self.param_shardings, None, rep),
            donate_argnums=(0, 1),
        )

    def step(self, tokens: np.ndarray, targets: np.ndarray) -> float:
        """One optimizer step; ends in the host's read of the loss. Its host
        work is three spans under ``lm.step``: the uploads, the dispatch of
        the jitted step, and the wait for the loss (a wait on the device,
        never a busy time)."""
        tracer = get_tracer()
        with tracer.span("lm.step"):
            with tracer.span("lm.input_put"):
                tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), self.batch_sharding)
                targets = jax.device_put(jnp.asarray(targets, jnp.int32), self.batch_sharding)
            with tracer.span("lm.dispatch"):
                self.params, self.opt_state, loss = self._train_step(
                    self.params, self.opt_state, self.constants, tokens,
                    targets)
            with tracer.span("lm.loss_wait"):
                if self.step_stats:
                    loss, *stats = np.asarray(loss).tolist()
                else:
                    loss = float(loss)
        registry = get_registry()
        registry.counter("fedml_lm_steps_total").inc()
        registry.counter("fedml_lm_tokens_total").inc(tokens.size)
        if self.step_stats:
            self.model.count_step_stats(
                registry, dict(zip(self.step_stats, stats)), dp=self.cfg.dp)
        return loss

    def train(self, data_iter, steps: int, log_every: int = 10, log_fn=print) -> list:
        losses = []
        for i in range(steps):
            tokens, targets = next(data_iter)
            loss = self.step(tokens, targets)
            losses.append(loss)
            if log_fn and i % log_every == 0:
                log_fn(f"[cheetah step {i}] loss={loss:.4f}")
        return losses

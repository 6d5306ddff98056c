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

from ..core.telemetry import get_registry, get_tracer
from ..models.transformer import TransformerLM
from .mesh import AXIS_DATA, AXIS_MODEL, AXIS_SEQ, MeshConfig, create_mesh
from .sharding import transformer_param_specs, tree_shardings

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
    """Compiled distributed causal-LM trainer (the Cheetah engine)."""

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
    ):
        tracer = get_tracer()
        with tracer.span("lm.trainer_init"):
            self.cfg = cfg
            self.mesh = mesh or make_lm_mesh(cfg)
            self.model = TransformerLM(
                vocab_size=vocab_size, dim=dim, num_heads=num_heads,
                num_layers=num_layers, max_len=max_len, dtype=dtype,
                seq_axis=AXIS_SEQ if cfg.sp > 1 else None,
                mesh=self.mesh,
                sp_impl=cfg.sp_impl,
                # per-block remat: O(1) layers of activations alive in bwd —
                # strictly better than checkpointing the whole apply (which
                # still holds every layer alive during the recompute)
                remat=(cfg.remat_policy if cfg.remat_policy != "full" else True)
                if cfg.use_remat else False,
            )
            with tracer.span("lm.init_params"):
                # init on host with a tiny batch, then place with TP
                # shardings; the init token length must divide by sp (ring
                # attention shards T)
                variables = self.model.init(
                    jax.random.PRNGKey(seed),
                    jnp.zeros((1, 8 * max(1, cfg.sp)), jnp.int32)
                )
                self.param_specs = transformer_param_specs(variables)
                self.param_shardings = tree_shardings(self.mesh, self.param_specs)
                self.params = jax.device_put(variables, self.param_shardings)
            with tracer.span("lm.opt_init"):
                self.opt = optax.adamw(
                    cfg.lr, weight_decay=cfg.weight_decay,
                    mu_dtype=jnp.dtype(cfg.mu_dtype) if cfg.mu_dtype else None)
                # moments inherit the params' shardings (init maps over
                # sharded params)
                self.opt_state = self.opt.init(self.params)
            self.batch_sharding = NamedSharding(self.mesh, P(AXIS_DATA, AXIS_SEQ))
            with tracer.span("lm.build_step"):
                self._train_step = self._build_train_step()

    def _build_train_step(self) -> Callable:
        model = self.model
        opt = self.opt
        ce_chunk = self.cfg.ce_chunk

        def loss_fn(params, tokens, targets):
            # block-level remat is baked into the model (cfg.use_remat)
            # the scopes are metadata: they name the ops of the loss and of
            # the optimizer in a device trace, and change no arithmetic
            if ce_chunk:
                from ..ops.losses import chunked_lm_cross_entropy

                hid = model.apply(params, tokens, return_hidden=True)
                with jax.named_scope("lm.loss"):
                    head = params["params"]["head"]["kernel"].astype(hid.dtype)
                    return chunked_lm_cross_entropy(hid, head, targets,
                                                    chunk=ce_chunk)
            logits = model.apply(params, tokens)
            with jax.named_scope("lm.loss"):
                logz = jax.nn.log_softmax(logits.astype(jnp.float32))
                ll = jnp.take_along_axis(logz, targets[..., None], -1)[..., 0]
                return -ll.mean()

        def train_step(params, opt_state, tokens, targets):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
            with jax.named_scope("lm.optimizer"):
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        rep = NamedSharding(self.mesh, P())
        return jax.jit(
            train_step,
            in_shardings=(self.param_shardings, None, self.batch_sharding, self.batch_sharding),
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
                    self.params, self.opt_state, tokens, targets
                )
            with tracer.span("lm.loss_wait"):
                loss = float(loss)
        registry = get_registry()
        registry.counter("fedml_lm_steps_total").inc()
        registry.counter("fedml_lm_tokens_total").inc(tokens.size)
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

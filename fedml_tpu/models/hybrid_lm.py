"""A decoder built from a ``layer_types`` list: the hybrid LMs whose layers
differ in kind by position.

Every layer is ``h += Op(RMSNorm(h)); h += FF(RMSNorm(h))``. ``Op`` is, by
``layer_types[i]``, a gated short convolution (``"conv"``, ops/short_conv.py)
or causal grouped-query attention with per-head RMS-normalised, rotary q and
k (``"full_attention"``, ops/rotary.py, ops/attention.py: the same dispatch
rule and flash kernels as ``TransformerLM``). ``FF`` is a dense SwiGLU in the
first ``num_dense_layers`` layers and a dropless routed-expert layer after
them (ops/moe.py ``RoutedExperts``: sigmoid scores, a selection bias,
normalised top-k weights), which may hold a chip's share of the experts.
No bias anywhere, no position table, the head tied to the embedding.
``DecoderConfig`` carries the published key names of such a model's
``config.json`` (LFM2-MoE's), so a configuration file maps onto it key by
key.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.moe import MOE_STATS, RoutedExperts

LAYER_KINDS = ("conv", "full_attention")


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int
    hidden_size: int
    layer_types: Tuple[str, ...]
    num_dense_layers: int
    intermediate_size: int          # the dense layers' SwiGLU width
    moe_intermediate_size: int      # one expert's SwiGLU width
    num_experts: int                # the router's width
    num_experts_per_tok: int
    num_attention_heads: int
    num_key_value_heads: int
    experts_held: Optional[Tuple[int, int]] = None  # (offset, count); all
    head_dim: Optional[int] = None  # hidden_size // heads where None
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(self.experts_held))
        unknown = set(self.layer_types) - set(LAYER_KINDS)
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}; this "
                             f"decoder has {LAYER_KINDS}")


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from ..ops.rotary import rms_norm

        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return rms_norm(x, scale, self.eps).astype(self.dtype)


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name,
                    kernel_init=nn.initializers.normal(0.02))


class ShortConv(nn.Module):
    """``(c * conv(b * x)) W_out`` with ``[b, c, x] = split3(u W_in)``."""

    dim: int
    taps: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        from ..ops.short_conv import gated_short_conv

        bcx = _dense(3 * self.dim, self.dtype, "in_proj")(u)
        taps = self.param("taps", nn.initializers.normal(0.02),
                          (self.dim, self.taps), jnp.float32)
        return _dense(self.dim, self.dtype, "out_proj")(
            gated_short_conv(bcx, taps))


class GroupedQueryAttention(nn.Module):
    """Causal attention, ``kv_heads`` KV heads serving ``heads`` query
    heads; q and k RMS-normalised per head, then rotary."""

    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    eps: float
    dtype: jnp.dtype = jnp.float32
    mesh: Optional[object] = None
    attn_impl: Optional[str] = None

    @nn.compact
    def __call__(self, u):
        from ..ops.rotary import apply_rotary

        B, T, _ = u.shape
        H, Hkv, Dh = self.heads, self.kv_heads, self.head_dim
        q = _dense(H * Dh, self.dtype, "q_proj")(u).reshape(B, T, H, Dh)
        k = _dense(Hkv * Dh, self.dtype, "k_proj")(u).reshape(B, T, Hkv, Dh)
        v = _dense(Hkv * Dh, self.dtype, "v_proj")(u).reshape(B, T, Hkv, Dh)
        q = apply_rotary(RMSNorm(self.eps, self.dtype, name="q_norm")(q),
                         self.rope_theta)
        k = apply_rotary(RMSNorm(self.eps, self.dtype, name="k_norm")(k),
                         self.rope_theta)
        out = self._local_attention(q, k, v).reshape(B, T, H * Dh)
        return _dense(self.dim, self.dtype, "o_proj")(out)

    def _local_attention(self, q, k, v):
        """The attention core: the scope a device trace names it by, as
        ``SelfAttention._local_attention`` is for ``TransformerLM``."""
        from ..ops.attention import local_attention

        return local_attention(q, k, v, causal=True, impl=self.attn_impl,
                               mesh=self.mesh)


class SwiGLU(nn.Module):
    dim: int
    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        gate = nn.silu(_dense(self.width, self.dtype, "w1")(u))
        return _dense(self.dim, self.dtype, "w2")(
            gate * _dense(self.width, self.dtype, "w3")(u))


class DecoderLayer(nn.Module):
    """One layer of kind ``kind``; ``dense`` picks its feed-forward.
    Returns (h, the expert layer's stats or zeros)."""

    cfg: DecoderConfig
    kind: str
    dense: bool
    dtype: jnp.dtype = jnp.float32
    mesh: Optional[object] = None
    attn_impl: Optional[str] = None

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        u = RMSNorm(c.norm_eps, self.dtype, name="operator_norm")(h)
        if self.kind == "conv":
            h = h + ShortConv(c.hidden_size, c.conv_L_cache, self.dtype,
                              name="conv")(u)
        else:
            h = h + GroupedQueryAttention(
                c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                c.head_dim or c.hidden_size // c.num_attention_heads,
                c.rope_theta, c.norm_eps, self.dtype, mesh=self.mesh,
                attn_impl=self.attn_impl, name="attn")(u)
        u = RMSNorm(c.norm_eps, self.dtype, name="ffn_norm")(h)
        if self.dense:
            return h + SwiGLU(c.hidden_size, c.intermediate_size, self.dtype,
                              name="mlp")(u), jnp.zeros(len(MOE_STATS), jnp.int32)
        out, stats = RoutedExperts(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, experts_held=c.experts_held,
            dtype=self.dtype, mesh=self.mesh, name="moe")(u)
        return h + out, stats


class HybridLM(nn.Module):
    """Decoder-only causal LM over ``cfg.layer_types``. ``__call__`` gives
    float32 logits (B, T, V), or the final hidden states with
    ``return_hidden``; with ``return_stats`` also the step's routing
    statistics ``STEP_STATS`` (counts summed over the expert layers, the
    largest load of any held expert in any layer)."""

    cfg: DecoderConfig
    dtype: jnp.dtype = jnp.float32
    mesh: Optional[object] = None
    attn_impl: Optional[str] = None
    remat: Union[bool, str] = False  # as TransformerLM: per layer

    STEP_STATS = MOE_STATS

    @nn.compact
    def __call__(self, tokens, train: bool = False,
                 return_hidden: bool = False, return_stats: bool = False):
        c = self.cfg
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(0.02),
                         name="embed")
        h = embed(tokens)
        if self.remat == "dots":
            layer_cls = nn.remat(
                DecoderLayer, policy=jax.checkpoint_policies.checkpoint_dots)
        elif self.remat in (True, "full"):
            layer_cls = nn.remat(DecoderLayer)
        elif not self.remat:
            layer_cls = DecoderLayer
        else:
            raise ValueError(
                f"unknown remat policy {self.remat!r}; use False, True, "
                "'full', or 'dots'")
        stats = jnp.zeros(len(MOE_STATS), jnp.int32)
        peak = MOE_STATS.index("held_load_max")
        for i, kind in enumerate(c.layer_types):
            h, s = layer_cls(c, kind, i < c.num_dense_layers, self.dtype,
                             mesh=self.mesh, attn_impl=self.attn_impl,
                             name=f"layer_{i}")(h)
            stats = (stats + s).at[peak].set(jnp.maximum(stats[peak], s[peak]))
        h = RMSNorm(c.norm_eps, self.dtype, name="final_norm")(h)
        if not return_hidden:
            with jax.named_scope("head"):
                # as one (B T, D) x (D, V) product: XLA:TPU then keeps the
                # vocabulary the logits' minor axis, which the loss reduces
                # over ("btd,vd->btv" made T minor at T = V = 8192; head and
                # loss alone ran 65.9 ms against 58.9: PERF.md section 6)
                B, T = tokens.shape
                h = jnp.dot(h.reshape(B * T, c.hidden_size),
                            embed.embedding.astype(self.dtype).T,
                            preferred_element_type=jnp.float32
                            ).reshape(B, T, c.vocab_size)
        return (h, stats) if return_stats else h

    @staticmethod
    def head_kernel(params):
        """The tied output head, (D, V), from the parameter tree."""
        return params["params"]["embed"]["embedding"].T

    def count_step_stats(self, registry, stats: dict, dp: int = 1) -> None:
        """A step's ``STEP_STATS`` into the registry: assignments held here
        and elsewhere, those dropped (a dropless layer's stays 0), and the
        fullest held expert's load over the mean held load (on any one
        device over a device's mean, under ``dp`` data-parallel devices)."""
        c = self.cfg
        counter = registry.counter
        counter("fedml_moe_assignments_total", held="yes").inc(stats["held"])
        counter("fedml_moe_assignments_total", held="no").inc(
            stats["total"] - stats["held"])
        counter("fedml_moe_dropped_total").inc(stats["dropped"])
        held = (c.experts_held or (0, c.num_experts))[1]
        layers = len(c.layer_types) - c.num_dense_layers
        if stats["held"]:
            registry.gauge("fedml_moe_held_load_max_over_mean").set(
                stats["held_load_max"] * held * layers * dp / stats["held"])

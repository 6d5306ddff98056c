"""A decoder built from a ``layer_types`` list: the hybrid LMs whose layers
differ in kind by position, and the looped ones that run one stack of layers
several times. Two families of kinds, by what a layer holds.

**Two-part layers**, ``h += Op(RMSNorm(h)); h += FF(RMSNorm(h))`` (LFM2-MoE's).
``Op`` is a gated short convolution (``"conv"``, ops/short_conv.py) or causal
grouped-query attention with per-head RMS-normalised, rotary q and k
(``"full_attention"``, ops/rotary.py, ops/attention.py: the same dispatch
rule and flash kernels as ``TransformerLM``; ``qk_norm`` false leaves the
normalisation out). With ``sandwich_norm`` each sub-layer's output is
normalised too, inside the residual: ``h += RMSNorm(Op(RMSNorm(h)))``
(Ouro's layers). ``FF`` is a dense SwiGLU in the
first ``num_dense_layers`` layers and a dropless routed-expert layer after
them (ops/moe.py ``RoutedExperts``: sigmoid scores, a selection bias,
normalised top-k weights), which may hold a chip's share of the experts.

**Window and global layers** (SmallThinker's). ``rope_layout`` says which
attention layers are rotary (1) and which have no position embedding at all
(0); ``sliding_window_layout`` which see a causal band of
``sliding_window_size`` keys, ``0 <= i - j < window`` (1), and which the
whole causal triangle (0): the flash kernels skip the key blocks outside
the band. The router may be a softmax over all the experts with no bias
(``moe_primary_router_apply_softmax``), the experts ReGLU (``mlp_hidden_act``
``"relu"``: ``W2 (relu(W1 u') * W3 u')``), and with ``early_router`` the
router reads the layer's first norm, the attention's input, and not the
second: ``u = RMSNorm(h); r = u W_r; h += Attn(u); u' = RMSNorm(h); h +=
Experts(u'; r)``.

**One-mixer blocks**, ``h += Mixer(RMSNorm(h))`` (Nemotron-H's; the kinds are
its ``layers_block_type`` names, and ``layer_types_of_pattern`` reads its
``hybrid_override_pattern``). ``Mixer`` is a Mamba-2 mixer (``"mamba"``,
ops/ssd.py: a chunked state-space scan between two projections), causal
grouped-query attention with no position embedding and no QK-norm
(``"attention"``), or an expert block (``"moe"``: the same ``RoutedExperts``
with ``mlp_hidden_act``'s expert form, ``routed_scaling_factor`` and a
shared expert ``moe_shared_expert_intermediate_size`` wide).

**The looped family** (``total_ut_steps`` = S > 1; Ouro's): the whole stack
is applied S times over one set of weights, ``h_t = final_norm(stack(h_{t-1}))``
with the final norm inside the loop, and an exit gate ``g_t = h_t w + b``
reads each pass's output. ``__call__`` gives the last pass's logits;
``return_passes`` every pass's hidden state and gate logit, which the
training objective takes (ops/losses.py ``expected_exit_loss``: the
expectation of the passes' losses under the gate's exit distribution).

No bias in any projection, no position table; the head is the embedding's
transpose, or with ``tie_word_embeddings`` false a matrix of its own.
``DecoderConfig`` carries the published key names of such models'
``config.json`` (LFM2-MoE's, Nemotron-H's, Ouro's, SmallThinker's), so a
configuration file maps onto it key by key.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.moe import MOE_STATS, RoutedExperts

TWO_PART_KINDS = ("conv", "full_attention")
MIXER_KINDS = ("mamba", "attention", "moe")
LAYER_KINDS = TWO_PART_KINDS + MIXER_KINDS
# ``mlp_hidden_act`` -> an expert's form (ops/moe.py ``EXPERT_FORMS``)
EXPERT_FORM_OF_ACT = {"silu": "swiglu", "relu2": "relu2", "relu": "reglu"}
PATTERN_KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


def ut_stats(passes: int) -> Tuple[str, ...]:
    """The names of a looped decoder's statistics of a step: each pass's
    mean token loss, then each pass's exit mass (its exit probability summed
    over the step's tokens)."""
    steps = range(1, passes + 1)
    return (tuple(f"ut_nll_{t}" for t in steps)
            + tuple(f"ut_mass_{t}" for t in steps))


def layer_types_of_pattern(pattern: str) -> Tuple[str, ...]:
    """``hybrid_override_pattern`` (a letter a block) as ``layer_types``."""
    unknown = sorted(set(pattern) - set(PATTERN_KINDS))
    if unknown:
        raise ValueError(f"hybrid_override_pattern holds {unknown}; this "
                         f"decoder reads {sorted(PATTERN_KINDS)}")
    return tuple(PATTERN_KINDS[letter] for letter in pattern)


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
    # the one-mixer blocks' (Nemotron-H's keys)
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    n_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    moe_shared_expert_intermediate_size: int = 0
    routed_scaling_factor: float = 1.0
    mlp_hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    # two facts of a two-part layer that LFM2 and Ouro state differently
    qk_norm: bool = True            # RMSNorm q and k per head before rotary
    sandwich_norm: bool = False     # a norm after each sub-layer as well
    # the looped family's (Ouro's key): the stack is applied this many times
    total_ut_steps: int = 1
    # window and global layers (SmallThinker's keys): a 0/1 a layer, None =
    # every attention layer rotary and causal over all of T
    sliding_window_size: Optional[int] = None
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    rope_layout: Optional[Tuple[int, ...]] = None
    # the routed experts' router: softmax with no bias, or sigmoid + bias;
    # ``early_router``: it reads the layer's first norm, before attention
    moe_primary_router_apply_softmax: bool = False
    early_router: bool = False

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(self.experts_held))
        unknown = set(self.layer_types) - set(LAYER_KINDS)
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}; this "
                             f"decoder has {LAYER_KINDS}")
        if self.mlp_hidden_act not in EXPERT_FORM_OF_ACT:
            raise ValueError(f"mlp_hidden_act {self.mlp_hidden_act!r}; this "
                             f"decoder has {sorted(EXPERT_FORM_OF_ACT)}")
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps {self.total_ut_steps}: the "
                             "stack is applied once at least")
        for key in ("sliding_window_layout", "rope_layout"):
            layout = getattr(self, key)
            if layout is None:
                continue
            object.__setattr__(self, key, tuple(int(x) for x in layout))
            if len(layout) != len(self.layer_types) or set(layout) - {0, 1}:
                raise ValueError(f"{key} holds a 0 or a 1 for each of the "
                                 f"{len(self.layer_types)} layers: {layout}")
        if any(self.sliding_window_layout or ()) and not self.sliding_window_size:
            raise ValueError("sliding_window_layout asks for windowed layers "
                             "and sliding_window_size gives no window")

    def attention_of(self, i: int) -> Tuple[bool, Optional[int]]:
        """Layer ``i``'s attention: (rotary, the window or None)."""
        rotary = self.rope_layout is None or bool(self.rope_layout[i])
        windowed = bool(self.sliding_window_layout
                        and self.sliding_window_layout[i])
        return rotary, (self.sliding_window_size if windowed else None)

    @property
    def expert_layers(self) -> int:
        """The layers that hold routed experts."""
        two_part = sum(kind in TWO_PART_KINDS for kind in self.layer_types)
        return (max(0, two_part - self.num_dense_layers)
                + self.layer_types.count("moe"))


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
    heads. Two facts a layer states: ``qk_norm``, q and k RMS-normalised per
    head; ``rotary``, q and k rotated by position (after the norm, where
    both). With neither there is no position embedding at all. ``window``:
    query i sees key j where ``0 <= i - j < window``."""

    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    eps: float
    dtype: jnp.dtype = jnp.float32
    mesh: Optional[object] = None
    attn_impl: Optional[str] = None
    rotary: bool = True
    qk_norm: bool = True
    window: Optional[int] = None  # a causal band of this many keys

    @nn.compact
    def __call__(self, u):
        from ..ops.rotary import apply_rotary

        B, T, _ = u.shape
        H, Hkv, Dh = self.heads, self.kv_heads, self.head_dim
        q = _dense(H * Dh, self.dtype, "q_proj")(u).reshape(B, T, H, Dh)
        k = _dense(Hkv * Dh, self.dtype, "k_proj")(u).reshape(B, T, Hkv, Dh)
        v = _dense(Hkv * Dh, self.dtype, "v_proj")(u).reshape(B, T, Hkv, Dh)

        def placed(x, norm_name):  # q or k on its way into the scores
            if self.qk_norm:
                x = RMSNorm(self.eps, self.dtype, name=norm_name)(x)
            return apply_rotary(x, self.rope_theta) if self.rotary else x

        q, k = placed(q, "q_norm"), placed(k, "k_norm")
        out = self._local_attention(q, k, v).reshape(B, T, H * Dh)
        return _dense(self.dim, self.dtype, "o_proj")(out)

    def _local_attention(self, q, k, v):
        """The attention core: the scope a device trace names it by, as
        ``SelfAttention._local_attention`` is for ``TransformerLM``."""
        from ..ops.attention import local_attention

        return local_attention(q, k, v, causal=True, impl=self.attn_impl,
                               mesh=self.mesh, window=self.window)


class SwiGLU(nn.Module):
    dim: int
    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        gate = nn.silu(_dense(self.width, self.dtype, "w1")(u))
        return _dense(self.dim, self.dtype, "w2")(
            gate * _dense(self.width, self.dtype, "w3")(u))


def _dt_bias_init(c: DecoderConfig):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform over
    [``time_step_min``, ``time_step_max``], floored at ``time_step_floor``."""
    def init(key, shape, dtype=jnp.float32):
        lo, hi = jnp.log(c.time_step_min), jnp.log(c.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi)),
                         c.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse
    return init


class Mamba2Mixer(nn.Module):
    """``mamba2_core(u W_in) W_out`` (ops/ssd.py): a causal convolution, the
    state-space scan over ``mamba_num_heads`` heads of ``mamba_head_dim``
    with a state ``ssm_state_size`` wide, and a gated group norm."""

    cfg: DecoderConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        from ..ops.ssd import mamba2_core

        c = self.cfg
        heads = c.mamba_num_heads
        inner = heads * c.mamba_head_dim
        conv_dim = inner + 2 * c.n_groups * c.ssm_state_size
        bound = c.conv_kernel ** -0.5  # torch's conv1d default
        uniform = lambda lo, hi: (  # noqa: E731
            lambda key, shape, dtype=jnp.float32: jax.random.uniform(
                key, shape, dtype, lo, hi))
        param = lambda name, init, *shape: self.param(  # noqa: E731
            name, init, shape, jnp.float32)
        zxbcdt = _dense(inner + conv_dim + heads, self.dtype, "in_proj")(u)
        y = mamba2_core(
            zxbcdt, param("conv_weight", uniform(-bound, bound), conv_dim,
                          c.conv_kernel),
            param("conv_bias", uniform(-bound, bound), conv_dim),
            param("dt_bias", _dt_bias_init(c), heads),
            param("A_log", lambda *a: jnp.log(uniform(1.0, 16.0)(*a)), heads),
            param("D", nn.initializers.ones, heads),
            param("norm", nn.initializers.ones, inner),
            heads=heads, head_dim=c.mamba_head_dim, state=c.ssm_state_size,
            groups=c.n_groups, chunk=c.chunk_size, eps=c.norm_eps)
        return _dense(c.hidden_size, self.dtype, "out_proj")(y)


class MixerBlock(nn.Module):
    """One block of kind ``kind``: ``h + Mixer(RMSNorm(h))``. Returns (h,
    the expert block's stats or zeros)."""

    cfg: DecoderConfig
    kind: str
    dtype: jnp.dtype = jnp.float32
    mesh: Optional[object] = None
    attn_impl: Optional[str] = None

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        u = RMSNorm(c.norm_eps, self.dtype, name="norm")(h)
        stats = jnp.zeros(len(MOE_STATS), jnp.int32)
        if self.kind == "mamba":
            out = Mamba2Mixer(c, self.dtype, name="mamba")(u)
        elif self.kind == "attention":
            out = GroupedQueryAttention(
                c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                c.head_dim or c.hidden_size // c.num_attention_heads,
                c.rope_theta, c.norm_eps, self.dtype, mesh=self.mesh,
                attn_impl=self.attn_impl, rotary=False, qk_norm=False,
                name="attn")(u)
        else:
            out, stats = RoutedExperts(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                c.num_experts_per_tok, experts_held=c.experts_held,
                dtype=self.dtype, mesh=self.mesh,
                form=EXPERT_FORM_OF_ACT[c.mlp_hidden_act],
                scale=c.routed_scaling_factor,
                shared_width=c.moe_shared_expert_intermediate_size,
                name="moe")(u)
        return h + out, stats


class DecoderLayer(nn.Module):
    """One two-part layer of kind ``kind``; ``dense`` picks its feed-forward.
    With ``cfg.sandwich_norm`` each sub-layer's output passes a norm of its
    own before it joins the residual. ``rotary`` and ``window`` are the
    layer's attention's (``DecoderConfig.attention_of``). Returns (h, the
    expert layer's stats or zeros)."""

    cfg: DecoderConfig
    kind: str
    dense: bool
    dtype: jnp.dtype = jnp.float32
    mesh: Optional[object] = None
    attn_impl: Optional[str] = None
    rotary: bool = True
    window: Optional[int] = None

    @nn.compact
    def __call__(self, h):
        c = self.cfg

        def joins(out, name):  # a sub-layer's output on its way to h
            if not c.sandwich_norm:
                return out
            return RMSNorm(c.norm_eps, self.dtype, name=name)(out)

        u = RMSNorm(c.norm_eps, self.dtype, name="operator_norm")(h)
        router_input = u if c.early_router else None
        if self.kind == "conv":
            out = ShortConv(c.hidden_size, c.conv_L_cache, self.dtype,
                            name="conv")(u)
        else:
            out = GroupedQueryAttention(
                c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                c.head_dim or c.hidden_size // c.num_attention_heads,
                c.rope_theta, c.norm_eps, self.dtype, mesh=self.mesh,
                attn_impl=self.attn_impl, rotary=self.rotary,
                qk_norm=c.qk_norm, window=self.window, name="attn")(u)
        h = h + joins(out, "operator_out_norm")
        u = RMSNorm(c.norm_eps, self.dtype, name="ffn_norm")(h)
        if self.dense:
            h = h + joins(SwiGLU(c.hidden_size, c.intermediate_size,
                                 self.dtype, name="mlp")(u), "ffn_out_norm")
            return h, jnp.zeros(len(MOE_STATS), jnp.int32)
        out, stats = RoutedExperts(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, experts_held=c.experts_held,
            dtype=self.dtype, mesh=self.mesh,
            form=EXPERT_FORM_OF_ACT[c.mlp_hidden_act],
            router="softmax" if c.moe_primary_router_apply_softmax
            else "sigmoid", name="moe")(u, router_input)
        return h + joins(out, "ffn_out_norm"), stats


class ExitGate(nn.Module):
    """A looped decoder's exit gate: one logit a position, ``h w + b`` in
    float32 (the exit distribution is made from it in float32)."""

    @nn.compact
    def __call__(self, h):
        w = self.param("kernel", nn.initializers.normal(0.02),
                       (h.shape[-1],), jnp.float32)
        b = self.param("bias", nn.initializers.zeros, (), jnp.float32)
        return jnp.dot(h.astype(jnp.float32), w) + b


class HybridLM(nn.Module):
    """Decoder-only causal LM over ``cfg.layer_types``, the stack applied
    ``cfg.total_ut_steps`` times over one set of weights. ``__call__`` gives
    float32 logits (B, T, V) of the last pass, or the final hidden states
    with ``return_hidden``; a looped decoder gives with ``return_passes``
    (every pass's final hidden state (S, B, T, D), every pass's gate logit
    (S, B, T) in float32). With ``return_stats`` also the routing statistics
    ``MOE_STATS`` (counts summed over the expert layers and the passes, the
    largest load of any held expert in any layer)."""

    cfg: DecoderConfig
    dtype: jnp.dtype = jnp.float32
    mesh: Optional[object] = None
    attn_impl: Optional[str] = None
    remat: Union[bool, str] = False  # as TransformerLM: per layer

    @property
    def STEP_STATS(self) -> Tuple[str, ...]:
        """What a training step hands back beside the loss: the routing
        statistics ``apply(..., return_stats=True)`` gives, and for a looped
        decoder ``ut_stats``, which the objective over its passes gives."""
        passes = self.cfg.total_ut_steps
        return MOE_STATS + (ut_stats(passes) if passes > 1 else ())

    @nn.compact
    def __call__(self, tokens, train: bool = False,
                 return_hidden: bool = False, return_stats: bool = False,
                 return_passes: bool = False):
        c = self.cfg
        looped = c.total_ut_steps > 1
        if return_passes and not looped:
            raise ValueError("return_passes: this decoder applies its stack "
                             "once (total_ut_steps = 1)")
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(0.02),
                         name="embed")
        h = embed(tokens)
        if self.remat == "dots":
            wrap = functools.partial(
                nn.remat, policy=jax.checkpoint_policies.checkpoint_dots)
        elif self.remat in (True, "full"):
            wrap = nn.remat
        elif not self.remat:
            wrap = lambda cls: cls  # noqa: E731
        else:
            raise ValueError(
                f"unknown remat policy {self.remat!r}; use False, True, "
                "'full', or 'dots'")
        layer_cls, block_cls = wrap(DecoderLayer), wrap(MixerBlock)
        # the layers are built once: a module called again uses its
        # parameters again, which is all a looped decoder's reuse is
        layers, two_part = [], 0
        for i, kind in enumerate(c.layer_types):
            kw = dict(mesh=self.mesh, attn_impl=self.attn_impl,
                      name=f"layer_{i}")
            if kind in MIXER_KINDS:
                layers.append(block_cls(c, kind, self.dtype, **kw))
            else:
                rotary, window = c.attention_of(i)
                layers.append(layer_cls(c, kind, two_part < c.num_dense_layers,
                                        self.dtype, rotary=rotary,
                                        window=window, **kw))
                two_part += 1
        final_norm = RMSNorm(c.norm_eps, self.dtype, name="final_norm")
        gate = ExitGate(name="exit_gate") if looped else None
        stats = jnp.zeros(len(MOE_STATS), jnp.int32)
        peak = MOE_STATS.index("held_load_max")
        hidden, gate_logits = [], []
        for _ in range(c.total_ut_steps):
            # (a scope only where there are passes to tell apart: the
            # one-pass decoders' op names stay as they were)
            with (jax.named_scope("ut.pass") if looped
                  else contextlib.nullcontext()):
                for layer in layers:
                    h, s = layer(h)
                    stats = (stats + s).at[peak].set(
                        jnp.maximum(stats[peak], s[peak]))
                h = final_norm(h)  # inside the loop: h_t feeds pass t + 1
            if looped:
                hidden.append(h)
                with jax.named_scope("ut.exit"):  # with the objective's ops
                    gate_logits.append(gate(h))
        if return_passes:
            h = (jnp.stack(hidden), jnp.stack(gate_logits))
        elif not return_hidden:
            untied = None if c.tie_word_embeddings else self.param(
                "lm_head", nn.initializers.normal(0.02),
                (c.hidden_size, c.vocab_size), jnp.float32)
            with jax.named_scope("head"):
                # as one (B T, D) x (D, V) product: XLA:TPU then keeps the
                # vocabulary the logits' minor axis, which the loss reduces
                # over ("btd,vd->btv" made T minor at T = V = 8192; head and
                # loss alone ran 65.9 ms against 58.9: PERF.md section 6)
                B, T = tokens.shape
                rows = h.reshape(B * T, c.hidden_size)
                head = (embed.embedding.astype(self.dtype).T if untied is None
                        else untied.astype(self.dtype))
                h = jnp.dot(rows, head, preferred_element_type=jnp.float32
                            ).reshape(B, T, c.vocab_size)
        return (h, stats) if return_stats else h

    @staticmethod
    def head_kernel(params):
        """The output head, (D, V), from the parameter tree: a matrix of
        its own, or the embedding's transpose where the two are tied."""
        p = params["params"]
        return p["lm_head"] if "lm_head" in p else p["embed"]["embedding"].T

    def count_step_stats(self, registry, stats: dict, dp: int = 1) -> None:
        """A step's ``STEP_STATS`` into the registry: assignments held here
        and elsewhere, those dropped (a dropless layer's stays 0), and the
        fullest held expert's load over the mean held load (on any one
        device over a device's mean, under ``dp`` data-parallel devices);
        for a looped decoder the passes run, each pass's exit mass and its
        mean token loss."""
        c = self.cfg
        counter = registry.counter
        if c.expert_layers:
            counter("fedml_moe_assignments_total", held="yes").inc(stats["held"])
            counter("fedml_moe_assignments_total", held="no").inc(
                stats["total"] - stats["held"])
            counter("fedml_moe_dropped_total").inc(stats["dropped"])
            held = (c.experts_held or (0, c.num_experts))[1]
            if stats["held"]:
                registry.gauge("fedml_moe_held_load_max_over_mean").set(
                    stats["held_load_max"] * held * c.expert_layers * dp
                    / stats["held"])
        if c.total_ut_steps > 1:
            counter("fedml_lm_ut_passes_total").inc(c.total_ut_steps)
            for t in range(1, c.total_ut_steps + 1):
                counter("fedml_lm_exit_mass_total", ut=t).inc(
                    stats[f"ut_mass_{t}"])
                registry.gauge("fedml_lm_ut_nll", ut=t).set(
                    stats[f"ut_nll_{t}"])

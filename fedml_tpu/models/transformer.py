"""Transformer LM + ViT.

Parity: the reference's transformer workloads live in ``app/fednlp`` (BERT
fine-tuning via HuggingFace) and FedCV; here transformers are first-class
in-tree models so the long-context / parallelism stack (ring attention over
the ``seq`` mesh axis, tensor parallel over ``model``) has a flagship to
drive. Attention routes through ``fedml_tpu.ops.attention`` so the same
module runs single-chip (fused softmax path) or sequence-sharded.
"""

from __future__ import annotations

from typing import Optional, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


def causal_mask(T: int, dtype=jnp.float32) -> jax.Array:
    return jnp.tril(jnp.ones((T, T), dtype=bool))


class MLPBlock(nn.Module):
    dim: int
    hidden_mult: int = 4
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(self.dim * self.hidden_mult, dtype=self.dtype)(x)
        h = nn.gelu(h)
        return nn.Dense(self.dim, dtype=self.dtype)(h)


class SelfAttention(nn.Module):
    dim: int
    num_heads: int
    causal: bool = True
    dtype: jnp.dtype = jnp.float32
    # ``mesh``: the mesh the enclosing step is partitioned over (None =
    # single device). sequence parallelism: when ``seq_axis`` is set,
    # attention runs sequence-sharded inside shard_map over that mesh axis.
    # ``sp_impl`` picks the collective pattern (ops/attention.py):
    #   "ring"    — K/V blocks rotate via ppermute, online softmax;
    #               O(T/n) memory per device (extreme context lengths).
    #   "ulysses" — two all-to-alls re-shard seq<->heads; full-sequence
    #               attention runs locally (flash-kernel eligible);
    #               needs num_heads % axis_size == 0.
    seq_axis: Optional[str] = None
    mesh: Optional[object] = None
    sp_impl: str = "ring"
    attn_impl: Optional[str] = None   # None = memory-aware auto (ops/attention)

    @nn.compact
    def __call__(self, x):
        from ..ops.attention import ring_attention, ulysses_attention

        B, T, D = x.shape
        H = self.num_heads
        qkv = nn.Dense(3 * self.dim, use_bias=False, dtype=self.dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        reshape = lambda t: t.reshape(B, T, H, D // H)  # noqa: E731
        q, k, v = reshape(q), reshape(k), reshape(v)
        if self.seq_axis is not None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            if self.sp_impl == "ulysses":
                sp_fn = lambda q, k, v: ulysses_attention(  # noqa: E731
                    q, k, v, self.seq_axis, causal=self.causal)
            elif self.sp_impl == "ring":
                sp_fn = lambda q, k, v: ring_attention(  # noqa: E731
                    q, k, v, self.seq_axis, causal=self.causal)
            else:
                raise ValueError(f"unknown sp_impl '{self.sp_impl}'")
            spec = P(None, self.seq_axis, None, None)
            out = shard_map(
                sp_fn,
                mesh=self.mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=False,
            )(q, k, v)
        else:
            out = self._local_attention(q, k, v)
        out = out.reshape(B, T, D)
        return nn.Dense(self.dim, use_bias=False, dtype=self.dtype, name="proj")(out)

    def _local_attention(self, q, k, v):
        """Attention with no sequence axis (``ops.attention.local_attention``
        under this module's mesh); the method is the scope the device trace
        names the attention core by."""
        from ..ops.attention import local_attention

        return local_attention(q, k, v, causal=self.causal,
                               impl=self.attn_impl, mesh=self.mesh)


class Block(nn.Module):
    dim: int
    num_heads: int
    causal: bool = True
    dtype: jnp.dtype = jnp.float32
    seq_axis: Optional[str] = None
    mesh: Optional[object] = None
    sp_impl: str = "ring"
    attn_impl: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        x = x + SelfAttention(
            self.dim, self.num_heads, self.causal, self.dtype,
            seq_axis=self.seq_axis, mesh=self.mesh, sp_impl=self.sp_impl,
            attn_impl=self.attn_impl,
        )(nn.LayerNorm(dtype=self.dtype)(x))
        x = x + MLPBlock(self.dim, dtype=self.dtype)(nn.LayerNorm(dtype=self.dtype)(x))
        return x


class TransformerLM(nn.Module):
    """Decoder-only causal LM."""

    vocab_size: int = 32000
    dim: int = 256
    num_heads: int = 8
    num_layers: int = 4
    max_len: int = 2048
    dtype: jnp.dtype = jnp.float32
    seq_axis: Optional[str] = None
    mesh: Optional[object] = None
    sp_impl: str = "ring"
    attn_impl: Optional[str] = None
    # rematerialize blocks in bwd (jax.checkpoint): False = save all
    # activations; True/"full" = recompute everything (O(1) activation HBM
    # per layer at ~1.3x fwd FLOPs); "dots" = checkpoint_dots policy —
    # matmul OUTPUTS are saved and only cheap elementwise/norm ops
    # recompute, trading some of full-remat's memory win to reclaim most
    # of its recompute FLOPs (the classic middle point on the
    # memory/compute curve; not measured on this chip)
    remat: Union[bool, str] = False

    # what ``DistributedLMTrainer`` asks of a model: the statistics a step
    # hands back beside the loss (none here), and the output head
    STEP_STATS = ()

    @staticmethod
    def head_kernel(params):
        """The output head, (D, V), from the parameter tree."""
        return params["params"]["head"]["kernel"]

    @nn.compact
    def __call__(self, tokens, train: bool = False,
                 return_hidden: bool = False):
        B, T = tokens.shape
        h = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype, name="wte")(tokens)
        pos = nn.Embed(self.max_len, self.dim, dtype=self.dtype, name="wpe")(
            jnp.arange(T)[None, :]
        )
        h = h + pos
        if self.remat == "dots":
            block_cls = nn.remat(
                Block, policy=jax.checkpoint_policies.checkpoint_dots)
        elif self.remat in (True, "full"):
            block_cls = nn.remat(Block)
        elif not self.remat:
            block_cls = Block
        else:
            # a typo'd policy string must not silently run full remat —
            # every 'dots' conclusion would actually measure the wrong mode
            raise ValueError(
                f"unknown remat policy {self.remat!r}; use False, True, "
                "'full', or 'dots'")
        for i in range(self.num_layers):
            h = block_cls(self.dim, self.num_heads, causal=True, dtype=self.dtype,
                          seq_axis=self.seq_axis, mesh=self.mesh,
                          sp_impl=self.sp_impl, attn_impl=self.attn_impl,
                          name=f"block_{i}")(h)
        h = nn.LayerNorm(dtype=self.dtype, name="ln_f")(h)
        if return_hidden:
            # for chunked-CE training (ops/losses.chunked_lm_cross_entropy):
            # the caller applies the head per sequence chunk so the full
            # (B, T, V) logits never materialize
            return h
        return nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype, name="head")(h)


def _encode_tokens(mod: nn.Module, tokens) -> jax.Array:
    """Shared bidirectional token encoder: embed + pos + blocks + final LN.

    A plain function (not a submodule) called from each task model's
    ``@nn.compact`` body, so the layers bind to the CALLER's scope and every
    task model keeps the flat wte/wpe/block_i/ln_f param tree (checkpoint
    compatible with the pre-factoring layout)."""
    T = tokens.shape[1]
    h = nn.Embed(mod.vocab_size, mod.dim, dtype=mod.dtype, name="wte")(tokens)
    pos = nn.Embed(mod.max_len, mod.dim, dtype=mod.dtype, name="wpe")(
        jnp.arange(T)[None, :]
    )
    h = h + pos
    for i in range(mod.num_layers):
        h = Block(mod.dim, mod.num_heads, causal=False, dtype=mod.dtype,
                  name=f"block_{i}")(h)
    return nn.LayerNorm(dtype=mod.dtype, name="ln_f")(h)


class TransformerClassifier(nn.Module):
    """Encoder + CLS-pool classifier — the FedNLP text-classification model
    family (reference ``app/fednlp/text_classification/model/bert_model.py``
    wraps HuggingFace BERT; here a native encoder sized for federated
    fine-tuning experiments)."""

    num_classes: int = 20
    vocab_size: int = 30522
    dim: int = 256
    num_heads: int = 8
    num_layers: int = 4
    max_len: int = 512
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        h = _encode_tokens(self, tokens)
        return nn.Dense(self.num_classes, dtype=self.dtype, name="cls")(h.mean(axis=1))


class TransformerTagger(nn.Module):
    """Encoder + per-token head — the FedNLP sequence-tagging family
    (reference ``app/fednlp/seq_tagging``: BERT token classification for NER).
    Output (B, T, num_tags); per-token labels ride the shared masked CE
    (the mask broadcasts over the token dim)."""

    num_tags: int = 9
    vocab_size: int = 30522
    dim: int = 256
    num_heads: int = 8
    num_layers: int = 4
    max_len: int = 512
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        h = _encode_tokens(self, tokens)
        return nn.Dense(self.num_tags, dtype=self.dtype, name="tag_head")(h)


class TransformerSpanExtractor(nn.Module):
    """Encoder + start/end span heads — the FedNLP span-extraction family
    (reference ``app/fednlp/span_extraction``: SQuAD-style QA, BERT with
    start/end logits). Output (B, 2, T): two position-classification
    problems (class dim = sequence positions), so labels (B, 2) =
    (start_idx, end_idx) ride the shared masked CE unchanged."""

    vocab_size: int = 30522
    dim: int = 256
    num_heads: int = 8
    num_layers: int = 4
    max_len: int = 512
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        h = _encode_tokens(self, tokens)
        span = nn.Dense(2, dtype=self.dtype, name="span_head")(h)  # (B, T, 2)
        return jnp.swapaxes(span, 1, 2)  # (B, 2, T): classes = positions


class CrossAttention(nn.Module):
    """Decoder-side attention over encoder memory (no causal constraint)."""

    dim: int
    num_heads: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, memory):
        from ..ops.attention import multihead_attention

        B, T, D = x.shape
        S = memory.shape[1]
        H = self.num_heads
        q = nn.Dense(self.dim, use_bias=False, dtype=self.dtype, name="q")(x)
        kv = nn.Dense(2 * self.dim, use_bias=False, dtype=self.dtype, name="kv")(memory)
        k, v = jnp.split(kv, 2, axis=-1)
        q = q.reshape(B, T, H, D // H)
        k = k.reshape(B, S, H, D // H)
        v = v.reshape(B, S, H, D // H)
        # dense impl: the flash kernel assumes len(q) == len(kv); cross
        # attention has T != S and S is small in the seq2seq family
        out = multihead_attention(q, k, v, causal=False, impl="dense")
        out = out.reshape(B, T, D)
        return nn.Dense(self.dim, use_bias=False, dtype=self.dtype, name="proj")(out)


class DecoderBlock(nn.Module):
    dim: int
    num_heads: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, memory):
        x = x + SelfAttention(self.dim, self.num_heads, causal=True,
                              dtype=self.dtype)(nn.LayerNorm(dtype=self.dtype)(x))
        x = x + CrossAttention(self.dim, self.num_heads, dtype=self.dtype)(
            nn.LayerNorm(dtype=self.dtype)(x), memory)
        x = x + MLPBlock(self.dim, dtype=self.dtype)(nn.LayerNorm(dtype=self.dtype)(x))
        return x


class Seq2SeqTransformer(nn.Module):
    """Encoder-decoder with cross-attention — the FedNLP seq2seq family
    (reference ``app/fednlp/seq2seq``: BART-style summarization/generation).

    TPU-shaped I/O contract: the input is ONE rectangle ``(B, src_len +
    tgt_len)`` = ``[source tokens | shifted decoder-input tokens]`` (teacher
    forcing packed by the data pipeline — static shapes, no ragged pairs);
    labels are the (B, tgt_len) target tokens. Output (B, tgt_len, vocab)."""

    vocab_size: int = 30522
    src_len: int = 64
    tgt_len: int = 32
    dim: int = 256
    num_heads: int = 8
    num_layers: int = 3
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        if tokens.shape[1] != self.src_len + self.tgt_len:
            # fail fast: Embed silently clamps out-of-range positions, so a
            # config/data width mismatch would otherwise degrade invisibly
            raise ValueError(
                f"Seq2SeqTransformer expects width src_len+tgt_len = "
                f"{self.src_len}+{self.tgt_len}, got {tokens.shape[1]} — "
                f"align src_seq_len/tgt_seq_len with the dataset's packing")
        B = tokens.shape[0]
        src = tokens[:, : self.src_len]
        dec_in = tokens[:, self.src_len:]
        wte = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype, name="wte")
        # encoder
        h = wte(src) + nn.Embed(self.src_len, self.dim, dtype=self.dtype,
                                name="enc_pos")(jnp.arange(src.shape[1])[None, :])
        for i in range(self.num_layers):
            h = Block(self.dim, self.num_heads, causal=False, dtype=self.dtype,
                      name=f"enc_{i}")(h)
        memory = nn.LayerNorm(dtype=self.dtype, name="enc_ln")(h)
        # decoder (causal self-attn + cross-attn into the encoder memory)
        d = wte(dec_in) + nn.Embed(self.tgt_len, self.dim, dtype=self.dtype,
                                   name="dec_pos")(jnp.arange(dec_in.shape[1])[None, :])
        for i in range(self.num_layers):
            d = DecoderBlock(self.dim, self.num_heads, dtype=self.dtype,
                             name=f"dec_{i}")(d, memory)
        d = nn.LayerNorm(dtype=self.dtype, name="dec_ln")(d)
        return nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype,
                        name="lm_head")(d)


class ViT(nn.Module):
    """Small vision transformer (FedCV-parity family)."""

    num_classes: int = 10
    patch: int = 4
    dim: int = 192
    num_heads: int = 3
    num_layers: int = 6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        B = x.shape[0]
        x = nn.Conv(self.dim, (self.patch, self.patch), (self.patch, self.patch),
                    dtype=self.dtype, name="patchify")(x.astype(self.dtype))
        x = x.reshape(B, -1, self.dim)
        cls = self.param("cls", nn.initializers.zeros, (1, 1, self.dim), self.dtype)
        x = jnp.concatenate([jnp.broadcast_to(cls, (B, 1, self.dim)), x], axis=1)
        pos = self.param("pos", nn.initializers.normal(0.02), (1, x.shape[1], self.dim), self.dtype)
        x = x + pos
        for i in range(self.num_layers):
            x = Block(self.dim, self.num_heads, causal=False, dtype=self.dtype, name=f"block_{i}")(x)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        return nn.Dense(self.num_classes, dtype=self.dtype, name="head")(x[:, 0])

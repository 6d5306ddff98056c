"""Plain reference for the LFM2-MoE training step (``lfm2_moe``: LFM2-24B-A2B),
in straightforward jax.numpy: loss, gradients and AdamW. float32 at matmul
precision "highest" by default; ``compute`` lowers every matmul's operands one
precision (the control). Nothing here imports the program; what it shares
with ``reference/lm.py`` (seeded keys, batches, the lowered matmul, AdamW) it
takes from there. The weights are made here from the seed as a flat dict
``name -> tensor`` (layer ``i``'s leaves are ``L<i>.<leaf>``), the selection
biases beside them; the runner copies both into the program.

**The layer equations**, from the Hugging Face implementation of ``lfm2_moe``
as remembered (there is no network here): what could not be confirmed is
listed under ``assumed`` in the configuration's file.

- ``RMSNorm(x; g) = x / sqrt(mean(x^2) + norm_eps) * g``, float32. No bias
  anywhere (``conv_bias`` false).
- Embedding: ``h = E[tokens]``; no position table, no scaling.
- Every layer: ``h = h + Op(RMSNorm(h; g_op))``, then
  ``h = h + FF(RMSNorm(h; g_ffn))``.
- ``Op``, kind ``conv``: ``[b, c, x] = split3(u W_in)``, ``W_in`` (D, 3D);
  ``z = b * x``; ``y_t = sum_{j < conv_L_cache} w_j * z_{t-j}`` (depthwise,
  causal, ``z_{<0} = 0``, weight (D, conv_L_cache)); ``Op = (c * y) W_out``.
- ``Op``, kind ``full_attention``: ``q = u W_q`` as (T, H, Dh), ``k = u W_k``,
  ``v = u W_v`` as (T, Hkv, Dh); ``q = RMSNorm_Dh(q; g_q)``, ``k = RMSNorm_Dh(k;
  g_k)`` per head; rotary positions on q and k (``rope_theta``, rotate-half,
  all Dh dims); causal ``softmax(q k^T / sqrt(Dh)) v``, each KV head serving
  H / Hkv consecutive query heads; ``Op = concat W_o``.
- ``FF``, the first ``num_dense_layers`` layers: ``W_2(silu(W_1 u) * W_3 u)``,
  width ``intermediate_size``.
- ``FF``, the expert layers: ``s = sigmoid(u W_g)`` over all ``router_width``
  experts, float32; ``sel = top_k(s + beta)`` (beta: the expert bias, for the
  choice only, no gradient); ``w = s[sel] / (sum s[sel] + 1e-6)``
  (``norm_topk_prob``), times ``routed_scaling_factor`` (1); ``FF = sum_{e in
  sel} w_e W_2^e(silu(W_1^e u) * W_3^e u)``, width ``moe_intermediate_size``.
  beta is held constant through the steps.
- Output: ``logits = RMSNorm(h_L; g_f) E^T`` (tied head); the loss is the mean
  token cross-entropy.
- AdamW; step ``t`` (from 1) runs at ``lr * min(1, t / warmup_steps)``.

**Departures, each because the configuration is a chip's share** (the
configuration's ``deployment``): the expert sum runs over ``sel`` within the
experts held (``num_experts`` of them from ``experts_held_offset``; the choice
and the normaliser still run over all ``router_width``), every held expert
applied to every token and masked by the choice; the expert bias is what the
seeded router needs to spread its load evenly, as a trained checkpoint's
does, found once at set-up on a batch of the pool that no checked step sees
(``balanced_biases``: without it the seeded layers' common component sends 5
x the mean load to some experts and a fortieth to others, and a share of 8
experts sees 9% of the assignments, or 17%, by the seed); the vocabulary is
the slice held (ids, logits and loss over ``vocab_size`` rows of E); the
layers are ``layer_types`` as cut. Tap ``j`` of the convolution weighs the position ``j``
back (torch's conv1d weight holds the taps in the other order: the same
family of functions under seeded weights). Attention is computed a block of
queries at a time: whole, the float32 scores of the cell are 17 GB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import lm

make_batches = lm.make_batches
Q_BLOCK = 512
EXPERT_LEAVES = ("ew1", "ew3", "ew2")  # (held, ., .): a norm per expert
ROUTER_BELOW = {"f32": "f32", "bf16": "f32", "fp8": "bf16"}  # float32 as stated
# ``balanced``'s fixed-point search at set-up: its moves, the most of one
SETUP_MOVES, SETUP_RATE = 100, 0.02


def shape_of(cfg: dict) -> tuple:
    """The configuration's shape as a hashable (a static argument)."""
    heads = cfg["num_attention_heads"]
    return (cfg["vocab_size"], cfg["hidden_size"], tuple(cfg["layer_types"]),
            cfg["num_dense_layers"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["router_width"],
            cfg["num_experts"], cfg["experts_held_offset"],
            cfg["num_experts_per_tok"], heads, cfg["num_key_value_heads"],
            cfg.get("head_dim") or cfg["hidden_size"] // heads,
            cfg["conv_L_cache"], cfg["norm_eps"],
            float(cfg["rope_parameters"]["rope_theta"]))


def leaf_shapes(shape: tuple) -> dict:
    """name -> (shape, "normal" | "ones") of every trained leaf, in order."""
    V, D, kinds, n_dense, F, Fe, _, held, _, _, H, Hkv, Dh, taps, *_ = shape
    out = {"embed": ((V, D), "normal"), "final_norm": ((D,), "ones")}
    for i, kind in enumerate(kinds):
        p = f"L{i}."
        out[p + "op_norm"] = ((D,), "ones")
        if kind == "conv":
            out[p + "conv_in"] = ((D, 3 * D), "normal")
            out[p + "conv_taps"] = ((D, taps), "normal")
            out[p + "conv_out"] = ((D, D), "normal")
        else:
            out[p + "wq"] = ((D, H * Dh), "normal")
            out[p + "wk"] = ((D, Hkv * Dh), "normal")
            out[p + "wv"] = ((D, Hkv * Dh), "normal")
            out[p + "wo"] = ((H * Dh, D), "normal")
            out[p + "q_norm"] = ((Dh,), "ones")
            out[p + "k_norm"] = ((Dh,), "ones")
        out[p + "ffn_norm"] = ((D,), "ones")
        if i < n_dense:
            out[p + "w1"] = ((D, F), "normal")
            out[p + "w3"] = ((D, F), "normal")
            out[p + "w2"] = ((F, D), "normal")
        else:
            out[p + "gate"] = ((D, shape[6]), "normal")
            out[p + "ew1"] = ((held, D, Fe), "normal")
            out[p + "ew3"] = ((held, D, Fe), "normal")
            out[p + "ew2"] = ((held, Fe, D), "normal")
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, shape: tuple, std):
    leaves = leaf_shapes(shape)
    weights = {
        name: (std * jax.random.normal(jax.random.fold_in(key, i), dims,
                                       jnp.float32)
               if how == "normal" else jnp.ones(dims, jnp.float32))
        for i, (name, (dims, how)) in enumerate(leaves.items())}
    biases = {
        f"L{i}.expert_bias": std * jax.random.normal(
            jax.random.fold_in(key, 10_000 + i), (shape[6],), jnp.float32)
        for i in range(shape[3], len(shape[2]))}
    return weights, biases


def init_weights(seed: int, cfg: dict):
    """(weights, the selection biases' seeded start), made in one jitted call
    on the device."""
    return _init(lm.seed_key(seed), shape_of(cfg), jnp.float32(cfg["init_std"]))


def seeded(seed: int, cfg: dict, traffic: dict):
    """(weights, selection biases) as a run starts from them: the seeded
    weights, and the biases balanced on the last batch of the seed's pool
    (the checked steps see the first ones)."""
    weights, start = init_weights(seed, cfg)
    tokens = make_batches(seed, cfg, traffic)[-1][:, :-1]
    return weights, balanced_biases(weights, start, jnp.asarray(tokens),
                                    shape_of(cfg))


@functools.partial(jax.jit, static_argnums=(3,))
def balanced_biases(weights, start, tokens, shape: tuple):
    """The selection biases that spread this router's choices evenly over
    its experts on ``tokens``, layer by layer in one float32 forward pass:
    what training with ``use_expert_bias`` leaves in a checkpoint (an expert
    chosen less than the mean has its bias raised, one chosen more has it
    lowered), searched for here since there is no training run to do it."""
    return forward(weights, start, tokens, shape, balance=True)[1]["biases"]


def balanced(scores, bias, k: int):
    """The bias that evens the load of ``top_k(scores + bias)`` on one
    batch's router scores (N, E), by fixed-point search: an expert chosen
    less than the mean has its bias raised, one chosen more lowered, in
    proportion, by at most ``SETUP_RATE``, ``SETUP_MOVES`` times."""
    rate, moves = SETUP_RATE, SETUP_MOVES

    def move(_, b):
        _, sel = jax.lax.top_k(scores + b, k)
        load = jax.nn.one_hot(sel, scores.shape[-1],
                              dtype=jnp.float32).sum((0, 1))
        return b + rate * jnp.clip(1 - load / load.mean(), -1, 1)
    return jax.lax.fori_loop(0, moves, move, bias)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rotary(x, theta):
    """x: (b, T, heads, Dh); rotate-half over all Dh dims."""
    T, Dh = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, compute):
    """Causal softmax(q k^T / sqrt(Dh)) v, a block of queries at a time.
    q: (b, T, H, Dh); k, v: (b, T, Hkv, Dh)."""
    b, T, H, Dh = q.shape
    Hkv = k.shape[2]
    block = min(Q_BLOCK, T)
    qb = q.reshape(b, T // block, block, Hkv, H // Hkv, Dh)
    cols = jnp.arange(T)

    @jax.checkpoint
    def one(args):
        qi, start = args  # (b, block, Hkv, G, Dh)
        s = lm._mm("bqkgd,bskd->bkgqs", qi, k, compute) / np.sqrt(Dh)
        keep = (start + jnp.arange(block))[:, None] >= cols[None, :]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        return lm._mm("bkgqs,bskd->bqkgd", p, v, compute)

    out = jax.lax.map(one, (jnp.moveaxis(qb, 1, 0),
                            jnp.arange(T // block) * block))
    return jnp.moveaxis(out, 0, 1).reshape(b, T, H * Dh)


def _experts(u, p, bias, shape, compute, capacity_drop, balance=False):
    """The held experts' part of the routed SwiGLU. u: (N, D). Returns
    (FF (N, D), the choice (N, k) over all the router's experts, the bias
    used: ``bias``, or with ``balance`` the one that evens the load)."""
    _, _, _, _, _, _, _, held, offset, k, *_ = shape
    s = jax.nn.sigmoid(lm._mm("nd,de->ne", u, p["gate"], ROUTER_BELOW[compute]))
    bias = jax.lax.stop_gradient(balanced(s, bias, k) if balance else bias)
    _, sel = jax.lax.top_k(s + bias, k)
    chosen = jax.nn.one_hot(sel, s.shape[-1], dtype=jnp.float32).sum(1)
    w = s * chosen
    w = w / (w.sum(-1, keepdims=True) + 1e-6)  # x routed_scaling_factor = 1
    if capacity_drop:
        # the planted fault: an expert takes its first C tokens, C = 1.0 x
        # the mean load, as a capacity-factor layer would, and drops the rest
        capacity = u.shape[0] * k // s.shape[-1]
        w = w * (jnp.cumsum(chosen, 0) * chosen <= capacity)
    out = jnp.zeros_like(u)
    for e in range(held):
        mid = jax.nn.silu(lm._mm("nd,df->nf", u, p["ew1"][e], compute)) * lm._mm(
            "nd,df->nf", u, p["ew3"][e], compute)
        out = out + w[:, offset + e, None] * lm._mm(
            "nf,fd->nd", mid, p["ew2"][e], compute)
    return out, sel, bias


def forward(w, biases, x, shape: tuple, compute: str = "f32",
            capacity_drop: bool = False, balance: bool = False):
    """tokens x: (b, T) -> (logits (b, T, V), {"choices": [each expert
    layer's choice], "biases": {name: the bias each used}})."""
    _, D, kinds, n_dense, _, _, _, _, _, _, H, Hkv, Dh, taps, eps, theta = shape
    b, T = x.shape
    h = w["embed"][x]
    choices, used = [], {}

    def layer(h, p, bias, kind, dense):
        u = _rms(h, p["op_norm"], eps)
        if kind == "conv":
            bb, c, xx = jnp.split(lm._mm("btd,de->bte", u, p["conv_in"],
                                         compute), 3, -1)
            z = bb * xx
            y = sum(jnp.pad(z, ((0, 0), (j, 0), (0, 0)))[:, :T]
                    * p["conv_taps"][:, j] for j in range(taps))
            h = h + lm._mm("btd,de->bte", c * y, p["conv_out"], compute)
        else:
            q = lm._mm("btd,de->bte", u, p["wq"], compute).reshape(b, T, H, Dh)
            k = lm._mm("btd,de->bte", u, p["wk"], compute).reshape(b, T, Hkv, Dh)
            v = lm._mm("btd,de->bte", u, p["wv"], compute).reshape(b, T, Hkv, Dh)
            q = _rotary(_rms(q, p["q_norm"], eps), theta)
            k = _rotary(_rms(k, p["k_norm"], eps), theta)
            h = h + lm._mm("bte,ed->btd", _attention(q, k, v, compute),
                           p["wo"], compute)
        u = _rms(h, p["ffn_norm"], eps)
        if dense:
            mid = jax.nn.silu(lm._mm("btd,df->btf", u, p["w1"], compute)) * lm._mm(
                "btd,df->btf", u, p["w3"], compute)
            return h + lm._mm("btf,fd->btd", mid, p["w2"], compute), None
        out, *routed = _experts(u.reshape(b * T, D), p, bias, shape, compute,
                                capacity_drop, balance)
        return h + out.reshape(b, T, D), routed

    for i, kind in enumerate(kinds):
        pre = f"L{i}."
        p = {name[len(pre):]: a for name, a in w.items() if name.startswith(pre)}
        h, routed = jax.checkpoint(layer, static_argnums=(3, 4))(
            h, p, biases.get(pre + "expert_bias"), kind, i < n_dense)
        if routed is not None:
            choices.append(routed[0])
            used[pre + "expert_bias"] = routed[1]
    logits = lm._mm("btd,vd->btv", _rms(h, w["final_norm"], eps), w["embed"],
                    compute)
    return logits, {"choices": choices, "biases": used}


def loss_sum(w, biases, tokens, shape, compute, capacity_drop):
    """Sum of token cross-entropies over the rows given. tokens: (b, T+1)."""
    logits, _ = forward(w, biases, tokens[:, :-1], shape, compute, capacity_drop)
    logz = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logz, tokens[:, 1:, None], -1).sum()


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def loss_and_grad(w, biases, tokens, shape, compute: str, capacity_drop: bool,
                  rows: int):
    """Mean loss and its gradient over the whole batch, ``rows`` sequences
    at a time so that float32 activations fit beside the weights."""
    blocks = tokens.reshape(-1, rows, tokens.shape[-1])
    n_tok = tokens.shape[0] * (tokens.shape[1] - 1)

    def one(acc, tb):
        return jax.tree.map(jnp.add, acc, jax.value_and_grad(loss_sum)(
            w, biases, tb, shape, compute, capacity_drop)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, w))
    (l, g), _ = jax.lax.scan(one, zero, blocks)
    return l / n_tok, jax.tree.map(lambda a: a / n_tok, g)


def leaf_norms(tree: dict) -> dict:
    """name -> l2 norm; an expert tensor gives one norm per expert,
    ``name/e``."""
    out = {}
    for name, a in tree.items():
        if name.rsplit(".", 1)[-1] in EXPERT_LEAVES:
            per = np.asarray(jnp.sqrt(jnp.sum(a.astype(jnp.float32) ** 2,
                                              axis=(1, 2))))
            out.update({f"{name}/{e}": float(x) for e, x in enumerate(per)})
        else:
            out[name] = float(jnp.sqrt(jnp.sum(a.astype(jnp.float32) ** 2)))
    return out


def readings(seed: int, cfg: dict, traffic: dict, compute: str = "f32",
             drop_half_batch: bool = False, capacity_drop: bool = False,
             rows: int = 1) -> dict:
    """Follow the first ``check_steps`` steps from the seed: each step's
    loss, the first gradient's norm per leaf, the norm of the parameters'
    change over those steps per leaf. ``drop_half_batch`` plants a step that
    trains on half of its rows, ``capacity_drop`` an expert layer that drops
    what is over its mean load."""
    o = cfg["optimizer"]
    shape = shape_of(cfg)
    w, biases = seeded(seed, cfg, traffic)
    batches = make_batches(seed, cfg, traffic)[:traffic["check_steps"]]
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, grad1 = [], None
    for t, tokens in enumerate(batches, start=1):
        if drop_half_batch:
            tokens = tokens[: len(tokens) // 2]
        loss, g = loss_and_grad(w, biases, jnp.asarray(tokens), shape, compute,
                                capacity_drop, min(rows, len(tokens)))
        losses.append(float(loss))
        if grad1 is None:
            grad1 = leaf_norms(g)
        lr = o["lr"] * min(1.0, t / o["warmup_steps"])
        w, m, v = lm.adamw(w, g, m, v, jnp.float32(t), lr, o["b1"], o["b2"],
                           o["eps"], o["weight_decay"])
        del g
    del m, v
    change = leaf_norms(jax.tree.map(jnp.subtract, w, init_weights(seed, cfg)[0]))
    return {"loss": losses, "grad1": grad1, "change": change}

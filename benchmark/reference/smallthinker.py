"""Plain reference for the SmallThinker training step (SmallThinker-21B-A3B:
window and global attention layers over routed ReGLU experts), in
straightforward jax.numpy: loss, gradients and AdamW. float32 at matmul
precision "highest" by default; ``compute`` lowers every product's operands
one precision (the control). Nothing here imports the program; what it shares
with ``reference/lm.py``, ``reference/lfm2.py`` and ``reference/ouro.py``
(seeded keys, batches, the lowered matmul, AdamW, RMSNorm, the rotary
embedding, the blocked head) it takes from there. The weights are made here
from the seed as a flat dict ``name -> tensor`` (layer ``i``'s leaves are
``L<i>.<leaf>``); the runner copies them into the program.

**The layer equations**, from the catalog's ``config`` and its
``described_as`` (there is no network here): what the keys do not state is
listed under ``assumed`` in the configuration's file. ``N`` = RMSNorm (``x /
sqrt(mean(x^2) + rms_norm_eps) * g``, float32), no bias anywhere.

    layer_l(h):  u = N1_l(h)
                 r = u W_r                           (router_width logits, float32: the router reads the attention's input)
                 q, k, v = u Wq, u Wk, u Wv          (H heads, Hkv KV heads of Dh, no QK-norm; each KV head serves H / Hkv
                                                      consecutive query heads)
                 q, k = rotary(q), rotary(k)         (rope_layout[l] = 1: rotate-half over all Dh, rope_theta; 0: no position)
                 h = h + softmax(q k^T / sqrt(Dh) + mask_l) v Wo
                                                     (mask_l: key j seen by query i where 0 <= i - j < sliding_window_size
                                                      if sliding_window_layout[l] = 1, where 0 <= i - j otherwise)
                 u' = N2_l(h)
                 p = softmax(r);  S = top_k(p);  w_e = p_e / sum_S p     (moe_primary_router_apply_softmax, norm_topk_prob)
                 h = h + sum_{e in S} w_e (relu(u' W1_e) * (u' W3_e)) W2_e   (ReGLU, width moe_ffn_hidden_size)
    model:       h = E[tokens];  h = layer_L(...layer_1(h));  logits = N_final(h) W_head (untied)
    loss:        the mean token cross-entropy; AdamW, step t (from 1) at lr * min(1, t / warmup_steps)

**Departures, each because the configuration is a chip's share** (the
configuration's ``deployment``): the expert sum runs over ``S`` within the
experts held (``moe_num_primary_experts`` of them from
``experts_held_offset``; the softmax, the choice and the normaliser still run
over all ``router_width``), every held expert applied to every token and
masked by the choice; the vocabulary is the slice held (ids, logits and loss
over ``vocab_size`` rows of E and columns of W_head); the layers are the
layouts as cut. Attention is computed a block of queries at a time against
every key, the band applied as a mask, and the head's product and token
losses a block of positions at a time, both under ``jax.checkpoint``: whole,
one layer's float32 scores at the cell's size are 30 GB.

**Faults** for ``limits.py``, planted here: ``drop_half_batch`` (the step
trains on the first half of its tokens), ``no_window`` (the windowed layers
see every earlier key), ``late_router`` (the router reads ``u'``, the
experts' input, and not the attention's).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import lfm2, lm, ouro

make_batches = lm.make_batches
Q_BLOCK = 256
EXPERT_LEAVES = ("ew1", "ew3", "ew2")  # (held, ., .): a norm per expert
ROUTER_BELOW = lfm2.ROUTER_BELOW       # the router is stated in float32
LAYER_LEAVES = ("n1", "wq", "wk", "wv", "wo", "gate", "n2", "ew1", "ew3", "ew2")


def shape_of(cfg: dict) -> tuple:
    """The configuration's shape as a hashable (a static argument)."""
    if not (cfg["moe_primary_router_apply_softmax"] and cfg["norm_topk_prob"]
            and cfg["mlp_hidden_act"] == "relu" and cfg["early_router"]
            and not cfg["tie_word_embeddings"]
            and cfg["rope_scaling"] is None):
        raise ValueError("this reference states a softmax router before "
                         "attention with normalised top-k weights, ReGLU "
                         "experts, an untied head and plain rotary positions")
    layers = cfg["num_hidden_layers"]
    if not len(cfg["sliding_window_layout"]) == len(cfg["rope_layout"]) == layers:
        raise ValueError("a layout holds one entry a layer")
    return (cfg["vocab_size"], cfg["hidden_size"], layers,
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["moe_ffn_hidden_size"], cfg["router_width"],
            cfg["moe_num_primary_experts"], cfg["experts_held_offset"],
            cfg["moe_num_active_primary_experts"], cfg["rms_norm_eps"],
            float(cfg["rope_theta"]), cfg["sliding_window_size"],
            tuple(cfg["sliding_window_layout"]), tuple(cfg["rope_layout"]))


def leaf_shapes(shape: tuple) -> dict:
    """name -> (shape, "normal" | "ones") of every trained leaf, in order."""
    V, D, layers, H, Hkv, Dh, F, width, held, *_ = shape
    out = {"embed": ((V, D), "normal"), "final_norm": ((D,), "ones"),
           "head": ((D, V), "normal")}
    dims = {"wq": (D, H * Dh), "wk": (D, Hkv * Dh), "wv": (D, Hkv * Dh),
            "wo": (H * Dh, D), "gate": (D, width), "ew1": (held, D, F),
            "ew3": (held, D, F), "ew2": (held, F, D)}
    for i in range(layers):
        for leaf in LAYER_LEAVES:
            out[f"L{i}.{leaf}"] = ((dims[leaf], "normal") if leaf in dims
                                   else ((D,), "ones"))
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, shape: tuple, std, embed_std):
    def leaf(i, name, dims, how):
        if how == "ones":
            return jnp.ones(dims, jnp.float32)
        return (embed_std if name == "embed" else std) * jax.random.normal(
            jax.random.fold_in(key, i), dims, jnp.float32)
    return {name: leaf(i, name, dims, how)
            for i, (name, (dims, how)) in enumerate(leaf_shapes(shape).items())}


def init_weights(seed: int, cfg: dict) -> dict:
    """The seeded weights, made in one jitted call on the device: normal(0,
    ``init_std``), the embedding normal(0, ``embed_init_std``), norms 1."""
    return _init(lm.seed_key(seed), shape_of(cfg), jnp.float32(cfg["init_std"]),
                 jnp.float32(cfg["embed_init_std"]))


def _attention(q, k, v, compute, window):
    """softmax(q k^T / sqrt(Dh)) v over the keys ``0 <= i - j < window``
    (``window`` None: ``0 <= i - j``), a block of queries at a time against
    every key. q: (b, T, H, Dh); k, v: (b, T, Hkv, Dh)."""
    b, T, H, Dh = q.shape
    Hkv = k.shape[2]
    block = min(Q_BLOCK, T)
    qb = q.reshape(b, T // block, block, Hkv, H // Hkv, Dh)
    cols = jnp.arange(T)

    @jax.checkpoint
    def one(args):
        qi, start = args  # (b, block, Hkv, G, Dh)
        s = lm._mm("bqkgd,bskd->bkgqs", qi, k, compute) / jnp.sqrt(
            jnp.float32(Dh))
        back = (start + jnp.arange(block))[:, None] - cols[None, :]  # i - j
        keep = back >= 0
        if window is not None:
            keep = keep & (back < window)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        return lm._mm("bkgqs,bskd->bqkgd", p, v, compute)

    out = jax.lax.map(one, (jnp.moveaxis(qb, 1, 0),
                            jnp.arange(T // block) * block))
    return jnp.moveaxis(out, 0, 1).reshape(b, T, H * Dh)


def _experts(u, r, p, shape, compute):
    """The held experts' part of the routed ReGLU. u: (N, D), the experts'
    input; r: (N, width), the router's logits."""
    held, offset, k = shape[8:11]
    probs = jax.nn.softmax(r, -1)
    _, sel = jax.lax.top_k(probs, k)
    chosen = jax.nn.one_hot(sel, probs.shape[-1], dtype=jnp.float32).sum(1)
    w = probs * chosen
    w = w / w.sum(-1, keepdims=True)
    out = jnp.zeros_like(u)
    for e in range(held):
        mid = jax.nn.relu(lm._mm("nd,df->nf", u, p["ew1"][e], compute)) * lm._mm(
            "nd,df->nf", u, p["ew3"][e], compute)
        out = out + w[:, offset + e, None] * lm._mm(
            "nf,fd->nd", mid, p["ew2"][e], compute)
    return out


def _layer(h, p, shape, windowed: bool, rotary: bool, compute,
           no_window: bool, late_router: bool):
    _, D, _, H, Hkv, Dh, *_ = shape
    eps, theta, window = shape[11:14]
    b, T, _ = h.shape
    u = lfm2._rms(h, p["n1"], eps)
    router = lambda x: lm._mm("btd,de->bte", x, p["gate"],  # noqa: E731
                              ROUTER_BELOW[compute])
    r = router(u)
    q = lm._mm("btd,de->bte", u, p["wq"], compute).reshape(b, T, H, Dh)
    k = lm._mm("btd,de->bte", u, p["wk"], compute).reshape(b, T, Hkv, Dh)
    v = lm._mm("btd,de->bte", u, p["wv"], compute).reshape(b, T, Hkv, Dh)
    if rotary:
        q, k = lfm2._rotary(q, theta), lfm2._rotary(k, theta)
    o = _attention(q, k, v, compute,
                   window if windowed and not no_window else None)
    h = h + lm._mm("bte,ed->btd", o, p["wo"], compute)
    u = lfm2._rms(h, p["n2"], eps)
    if late_router:
        r = router(u)
    out = _experts(u.reshape(b * T, D), r.reshape(b * T, -1), p, shape, compute)
    return h + out.reshape(b, T, D)


def loss_sum(w, tokens, shape, compute="f32", no_window=False,
             late_router=False):
    """Sum of token cross-entropies over the rows given. tokens: (b, T+1)."""
    layers, eps = shape[2], shape[11]
    windows, ropes = shape[14], shape[15]
    h = w["embed"][tokens[:, :-1]]
    for i in range(layers):
        pre = f"L{i}."
        p = {n[len(pre):]: a for n, a in w.items() if n.startswith(pre)}
        h = jax.checkpoint(_layer, static_argnums=tuple(range(2, 8)))(
            h, p, shape, bool(windows[i]), bool(ropes[i]), compute, no_window,
            late_router)
    h = lfm2._rms(h, w["final_norm"], eps)
    return ouro.token_nll(h, w["head"], tokens[:, 1:], compute).sum()


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def loss_and_grad(w, tokens, shape, compute: str, no_window: bool,
                  late_router: bool):
    """Mean loss and its gradient over the batch, a row at a time so that
    float32 activations fit beside the weights."""
    n_tok = tokens.shape[0] * (tokens.shape[1] - 1)

    def of(tb):
        return jax.value_and_grad(loss_sum)(w, tb[None], shape, compute,
                                            no_window, late_router)

    if len(tokens) == 1:  # the cell's one row: no second copy of the sums
        l, g = of(tokens[0])
    else:
        zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, w))
        (l, g), _ = jax.lax.scan(
            lambda acc, tb: (jax.tree.map(jnp.add, acc, of(tb)), None),
            zero, tokens)
    return l / n_tok, jax.tree.map(lambda a: a / n_tok, g)


leaf_norms = lfm2.leaf_norms  # an expert tensor's norm a held expert: EXPERT_LEAVES


def readings(seed: int, cfg: dict, traffic: dict, compute: str = "f32",
             drop_half_batch: bool = False, no_window: bool = False,
             late_router: bool = False) -> dict:
    """Follow the first ``check_steps`` steps from the seed: each step's
    loss, the first gradient's norm per leaf, the norm of the parameters'
    change over those steps per leaf. The keywords plant the module
    docstring's faults (``drop_half_batch``: half of the rows; of a batch of
    one row, that row's first half)."""
    o = cfg["optimizer"]
    shape = shape_of(cfg)
    w = init_weights(seed, cfg)
    batches = make_batches(seed, cfg, traffic)[:traffic["check_steps"]]
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, grad1 = [], None
    for t, tokens in enumerate(batches, start=1):
        if drop_half_batch:
            tokens = (tokens[: len(tokens) // 2] if len(tokens) > 1
                      else tokens[:, : tokens.shape[1] // 2 + 1])
        loss, g = loss_and_grad(w, jnp.asarray(tokens), shape, compute,
                                no_window, late_router)
        losses.append(float(loss))
        if grad1 is None:
            grad1 = leaf_norms(g)
        lr = o["lr"] * min(1.0, t / o["warmup_steps"])
        w, m, v = lm.adamw(w, g, m, v, jnp.float32(t), lr, o["b1"], o["b2"],
                           o["eps"], o["weight_decay"])
        del g
    del m, v
    change = leaf_norms(jax.tree.map(jnp.subtract, w, init_weights(seed, cfg)))
    return {"loss": losses, "grad1": grad1, "change": change}

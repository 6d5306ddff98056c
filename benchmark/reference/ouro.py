"""Plain reference for the Ouro training step (``ouro``: Ouro-2.6B, a looped
language model), in straightforward jax.numpy: loss, gradients and AdamW.
float32 at matmul precision "highest" by default; ``compute`` lowers every
product's operands one precision (the control). Nothing here imports the
program; what it shares with ``reference/lm.py`` and ``reference/lfm2.py``
(seeded keys, batches, the lowered matmul, AdamW, RMSNorm, the rotary
embedding, the blocked attention) it takes from there. The weights are made
here from the seed as a flat dict ``name -> tensor`` (layer ``i``'s leaves are
``L<i>.<leaf>``); the runner copies them into the program.

**The equations**, from the catalog's ``config`` and the family's report
("Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741) and
Hugging Face implementation as remembered (there is no network here): what
could not be confirmed is listed under ``assumed`` in the configuration's
file. ``N`` = RMSNorm (``x / sqrt(mean(x^2) + rms_norm_eps) * g``, float32),
``S = total_ut_steps``, ``L`` layers, no bias in any projection.

    layer_l(h):  a = N1_l(h);  q, k, v = a Wq, a Wk, a Wv   (H heads x Dh, as many KV heads, no QK-norm)
                 q, k = rotary(q), rotary(k)                 (rotate-half over all Dh, rope_theta, positions 0..T-1)
                 h = h + N2_l( causal_softmax(q k^T / sqrt(Dh)) v Wo )
                 u = N3_l(h);  h = h + N4_l( (silu(u W1) * (u W3)) W2 )
    model:       h_0 = embed[tokens]
                 for t = 1..S:  x = h_{t-1};  for l = 1..L: x = layer_l(x)   (the same weights every t)
                                h_t = N_final(x)                              (inside the loop: h_t feeds pass t+1)
                                g_t = h_t w_gate + b_gate        (B, T)       (one gate, shared by the passes)
                                z_t = h_t W_head                 (B, T, V)    (one untied head, shared)
    exit:        lam_t = sigmoid(g_t);  p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j), 1 < t < S;
                 p_S = prod_{j<S}(1 - lam_j)                                  (lam_S enters nothing)
    loss:        nll_t = -log softmax(z_t)[target]               (B, T)
                 loss = mean over tokens of [ sum_t p_t nll_t - beta H(p) ],  H(p) = -sum_t p_t log p_t

The sandwich norms (N2, N4: a sub-layer's output normalised inside the
residual), the final norm inside the loop and the gate's bias are the
family's as remembered; ``beta`` is ``exit_entropy_beta`` of the
configuration's file. AdamW, a constant rate.

**Departures**: none from the equations; the layers are ``layer_types`` as
cut (the configuration's ``deployment``: one pipeline stage's layers, run
``S`` times as the whole stack is). Attention is computed a block of queries
at a time and each pass's head product and token losses a block of
positions at a time (``HEAD_BLOCK``), both under ``jax.checkpoint``: whole,
one pass's float32 logits at the cell's size are 805 MB and four passes keep
four.

**Faults** for ``limits.py``, planted here: ``drop_half_batch`` (the step
trains on the first half of its tokens), ``pass_dropped`` (S - 1 passes),
``last_pass_only`` (the loss is the last pass's mean token loss: expectation
and entropy left out), ``gate_stopped`` (no gradient reaches the gate or
flows through ``p``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import lfm2, lm

make_batches = lm.make_batches
HEAD_BLOCK = 512
LAYER_LEAVES = ("n1", "wq", "wk", "wv", "wo", "n2", "n3", "w1", "w3", "w2", "n4")
GATE_BELOW = lfm2.ROUTER_BELOW  # the gate is stated in float32, as a router


def shape_of(cfg: dict) -> tuple:
    """The configuration's shape as a hashable (a static argument)."""
    if (cfg["num_key_value_heads"] != cfg["num_attention_heads"]
            or set(cfg["layer_types"]) != {"full_attention"}
            or cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu"
            or cfg["use_sliding_window"] or cfg["rope_scaling"] is not None
            or cfg["early_exit_threshold"] != 1):
        raise ValueError("this reference states full-attention layers of as "
                         "many KV heads as heads, SiLU, an untied head, plain "
                         "rotary positions and no pass skipped")
    return (cfg["vocab_size"], cfg["hidden_size"], len(cfg["layer_types"]),
            cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["head_dim"], cfg["rms_norm_eps"], float(cfg["rope_theta"]),
            cfg["total_ut_steps"], cfg["exit_entropy_beta"])


def leaf_shapes(shape: tuple) -> dict:
    """name -> (shape, "normal" | "ones" | "zeros") of every trained leaf."""
    V, D, layers, F, H, Dh, *_ = shape
    out = {"embed": ((V, D), "normal"), "final_norm": ((D,), "ones"),
           "head": ((D, V), "normal"), "gate_w": ((D,), "normal"),
           "gate_b": ((), "zeros")}
    dims = {"wq": (D, H * Dh), "wk": (D, H * Dh), "wv": (D, H * Dh),
            "wo": (H * Dh, D), "w1": (D, F), "w3": (D, F), "w2": (F, D)}
    for i in range(layers):
        for leaf in LAYER_LEAVES:
            out[f"L{i}.{leaf}"] = ((dims[leaf], "normal") if leaf in dims
                                   else ((D,), "ones"))
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, shape: tuple, std):
    made = {"normal": lambda k, dims: std * jax.random.normal(k, dims, jnp.float32),
            "ones": lambda k, dims: jnp.ones(dims, jnp.float32),
            "zeros": lambda k, dims: jnp.zeros(dims, jnp.float32)}
    return {name: made[how](jax.random.fold_in(key, i), dims)
            for i, (name, (dims, how)) in enumerate(leaf_shapes(shape).items())}


def init_weights(seed: int, cfg: dict) -> dict:
    """The seeded weights, made in one jitted call on the device."""
    return _init(lm.seed_key(seed), shape_of(cfg), jnp.float32(cfg["init_std"]))


def exit_distribution(g):
    """p (S, ...) from the gate logits g (S, ...), as the docstring's."""
    lam = jax.nn.sigmoid(g)
    stay = jnp.cumprod(1 - lam[:-1], 0)  # prod_{j<=t}(1 - lam_j), t < S
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([lam[:-1] * before, stay[-1:]])


def _layer(h, p, shape, compute):
    _, _, _, _, H, Dh, eps, theta, *_ = shape
    b, T, _ = h.shape
    a = lfm2._rms(h, p["n1"], eps)
    q, k, v = (lm._mm("btd,de->bte", a, p[w], compute).reshape(b, T, H, Dh)
               for w in ("wq", "wk", "wv"))
    o = lfm2._attention(lfm2._rotary(q, theta), lfm2._rotary(k, theta), v,
                        compute)
    h = h + lfm2._rms(lm._mm("bte,ed->btd", o, p["wo"], compute), p["n2"], eps)
    u = lfm2._rms(h, p["n3"], eps)
    mid = jax.nn.silu(lm._mm("btd,df->btf", u, p["w1"], compute)) * lm._mm(
        "btd,df->btf", u, p["w3"], compute)
    return h + lfm2._rms(lm._mm("btf,fd->btd", mid, p["w2"], compute),
                         p["n4"], eps)


def passes(w, x, shape: tuple, compute: str = "f32", pass_dropped: bool = False):
    """tokens x: (b, T) -> (each pass's final hidden state (S, b, T, D),
    each pass's gate logit (S, b, T))."""
    layers, steps = shape[2], shape[8] - int(pass_dropped)
    eps = shape[6]
    h = w["embed"][x]
    hidden, gates = [], []
    for _ in range(steps):
        for i in range(layers):
            pre = f"L{i}."
            p = {n[len(pre):]: a for n, a in w.items() if n.startswith(pre)}
            h = jax.checkpoint(_layer, static_argnums=(2, 3))(h, p, shape, compute)
        h = lfm2._rms(h, w["final_norm"], eps)
        hidden.append(h)
        gates.append(lm._mm("btd,d->bt", h, w["gate_w"], GATE_BELOW[compute])
                     + w["gate_b"])
    return jnp.stack(hidden), jnp.stack(gates)


def token_nll(h, head, targets, compute):
    """(b, T) token losses of one pass, ``HEAD_BLOCK`` positions at a time."""
    b, T, D = h.shape
    block = min(HEAD_BLOCK, T)
    hb = jnp.moveaxis(h.reshape(b, T // block, block, D), 1, 0)
    tb = jnp.moveaxis(targets.reshape(b, T // block, block), 1, 0)

    @jax.checkpoint
    def one(args):
        hi, ti = args
        logz = jax.nn.log_softmax(lm._mm("btd,dv->btv", hi, head, compute), -1)
        return -jnp.take_along_axis(logz, ti[..., None], -1)[..., 0]

    return jnp.moveaxis(jax.lax.map(one, (hb, tb)), 0, 1).reshape(b, T)


def forward(w, x, shape: tuple, compute: str = "f32"):
    """tokens x: (b, T) -> the last pass's logits (b, T, V): what the model
    gives at inference (``early_exit_threshold`` 1: no pass is skipped)."""
    hidden, _ = passes(w, x, shape, compute)
    return lm._mm("btd,dv->btv", hidden[-1], w["head"], compute)


def loss_sum(w, tokens, shape, compute="f32", pass_dropped=False,
             last_pass_only=False, gate_stopped=False):
    """Sum over the tokens given of ``sum_t p_t nll_t - beta H(p)``.
    tokens: (b, T+1)."""
    beta = shape[9]
    hidden, gates = passes(w, tokens[:, :-1], shape, compute, pass_dropped)
    nll = jnp.stack([token_nll(h, w["head"], tokens[:, 1:], compute)
                     for h in hidden])
    if last_pass_only:
        return nll[-1].sum()
    p = exit_distribution(gates)
    if gate_stopped:
        p = jax.lax.stop_gradient(p)
    entropy = -(p * jnp.log(jnp.maximum(p, 1e-30))).sum(0)
    return ((p * nll).sum(0) - beta * entropy).sum()


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def loss_and_grad(w, tokens, shape, compute: str, pass_dropped: bool,
                  last_pass_only: bool, gate_stopped: bool, rows: int):
    """Mean loss and its gradient over the whole batch, ``rows`` sequences
    at a time so that float32 activations fit beside the weights."""
    blocks = tokens.reshape(-1, rows, tokens.shape[-1])
    n_tok = tokens.shape[0] * (tokens.shape[1] - 1)

    def of(tb):
        return jax.value_and_grad(loss_sum)(
            w, tb, shape, compute, pass_dropped, last_pass_only, gate_stopped)

    if len(blocks) == 1:  # the cell's one row: no second copy of the sums
        l, g = of(blocks[0])
    else:
        zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, w))
        (l, g), _ = jax.lax.scan(
            lambda acc, tb: (jax.tree.map(jnp.add, acc, of(tb)), None),
            zero, blocks)
    return l / n_tok, jax.tree.map(lambda a: a / n_tok, g)


def leaf_norms(tree: dict) -> dict:
    """name -> l2 norm of every leaf."""
    return {name: float(jnp.sqrt(jnp.sum(a.astype(jnp.float32) ** 2)))
            for name, a in tree.items()}


def readings(seed: int, cfg: dict, traffic: dict, compute: str = "f32",
             drop_half_batch: bool = False, pass_dropped: bool = False,
             last_pass_only: bool = False, gate_stopped: bool = False,
             rows: int = 1) -> dict:
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
                                pass_dropped, last_pass_only, gate_stopped,
                                min(rows, len(tokens)))
        losses.append(float(loss))
        if grad1 is None:
            grad1 = leaf_norms(g)
        w, m, v = lm.adamw(w, g, m, v, jnp.float32(t), o["lr"], o["b1"],
                           o["b2"], o["eps"], o["weight_decay"])
        del g
    del m, v
    change = leaf_norms(jax.tree.map(jnp.subtract, w, init_weights(seed, cfg)))
    return {"loss": losses, "grad1": grad1, "change": change}

"""Losses with padding masks.

Masked variants are load-bearing: the rectangular client packing
(``data/federated.py``) pads small clients with zero rows, and the mask keeps
padding out of both the loss and the gradient — the TPU answer to the
reference's ragged Python loops (SURVEY.md §7 hard parts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean CE over the batch. logits (..., C), labels (...) int."""
    logz = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logz, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def _broadcast_mask(mask: jax.Array, target_ndim: int) -> jax.Array:
    """Per-example mask -> per-target mask (LM labels add a token dim)."""
    while mask.ndim < target_ndim:
        mask = mask[..., None]
    return mask


def masked_softmax_cross_entropy(
    logits: jax.Array, labels: jax.Array, mask: jax.Array
) -> jax.Array:
    """Sum(CE * mask) / max(sum(mask), 1). Shapes: logits (..., C), labels (...)
    and mask broadcastable to labels (a per-example mask covers per-token
    labels: every token of a padded example is masked)."""
    logz = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logz, labels[..., None], axis=-1)[..., 0]
    m = jnp.broadcast_to(_broadcast_mask(mask, ll.ndim), ll.shape)
    denom = jnp.maximum(m.sum(), 1.0)
    return -(ll * m).sum() / denom


def masked_accuracy(logits: jax.Array, labels: jax.Array, mask: jax.Array):
    """Returns (num_correct, num_valid) so callers can aggregate exactly."""
    pred = jnp.argmax(logits, axis=-1)
    m = jnp.broadcast_to(_broadcast_mask(mask, labels.ndim), labels.shape)
    correct = ((pred == labels) * m).sum()
    return correct, m.sum()


def lm_token_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-token negative log-likelihood, float32 (...): logsumexp - picked
    logit. logits (..., V) in the model's dtype, targets (...) int with
    0 <= target < V: a target that names no column (an ignore id, a negative
    one, an id of another chip's share of the vocabulary) picks the row's
    max and gets a plain softmax back, silently, where ``take_along_axis``
    wrapped or gave NaN.

    The backward hands ``(softmax - onehot) * g`` straight back in the
    logits' dtype, from the logits as they came and the (...) logsumexp: no
    (..., V) float32 array is kept from the forward. Written this way, and
    not as ``log_softmax`` + ``take_along_axis``, because XLA:TPU turns
    ``x - max(x)`` on 3-D logits with V <= 8192 into a full-width
    ``reduce-window`` (PERF.md section 6, PR 30)."""
    return _token_nll(logits, targets, jnp.arange(logits.shape[-1]))


@jax.custom_vjp
def _token_nll(logits, targets, cols):
    # cols = arange(V), made by the caller: an array made from no input
    # inside chunked_lm_cross_entropy's loop is moved out of it by scan's
    # partial evaluation, and out of the caller's named scope with it
    return _token_nll_fwd(logits, targets, cols)[0]


def _token_nll_fwd(logits, targets, cols):
    x = logits.astype(jnp.float32)
    m = x.max(-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.exp(x - m).sum(-1))
    # the picked logit by a one-hot select in the same pass as the sum (no
    # gather over a float32 copy of the logits). Off the target the row's
    # max, so the least is the picked logit: a constant there (0 under a
    # sum) is moved out of the chunked loop too, and kept whole at
    # (B, chunk, V)
    picked = jnp.where(targets[..., None] == cols, x, m).min(-1)
    return lse - picked, (logits, targets, cols, lse)


def _token_nll_bwd(res, g):
    logits, targets, cols, lse = res
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    d = (p - (targets[..., None] == cols)) * g[..., None]
    return d.astype(logits.dtype), None, None


_token_nll.defvjp(_token_nll_fwd, _token_nll_bwd)


def lm_cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token CE over every position, float32 statistics whatever
    the logits' dtype. logits (..., V), targets (...) int."""
    return lm_token_nll(logits, targets).mean()


def chunked_lm_cross_entropy(hidden: jax.Array, head_kernel: jax.Array,
                             targets: jax.Array,
                             chunk: int = 256) -> jax.Array:
    """Mean next-token CE WITHOUT materializing the full (B, T, V) f32
    logits tensor — the HBM hog of large-vocab LM training (V=32k at
    T=8k/B=4 is 4 GB in f32, times the bwd copies).

    Computes ``hidden @ head_kernel`` and ``lm_token_nll``'s arithmetic one
    sequence chunk at a time under ``lax.map``; peak extra memory is
    O(B * chunk * V) and the bwd re-derives each chunk's logits from the
    (tiny) saved hidden chunk. hidden (B, T, D), head_kernel (D, V),
    targets (B, T) int. T must be divisible by ``chunk`` (pad upstream)."""
    B, T, D = hidden.shape
    if T % chunk:
        raise ValueError(f"T={T} not divisible by chunk={chunk}")
    hc = hidden.reshape(B, T // chunk, chunk, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, T // chunk, chunk).transpose(1, 0, 2)
    cols = jnp.arange(head_kernel.shape[-1])

    @jax.checkpoint
    def one(args):
        # checkpointed: without it lax.map's backward saves each chunk's
        # logits — the full (B, T, V) tensor in disguise. Recomputing the
        # chunk logits from the (tiny) saved hidden chunk is the whole
        # point of this op.
        h, t = args
        return _token_nll(h @ head_kernel, t, cols)

    return jnp.mean(jax.lax.map(one, (hc, tc)))


def exit_distribution(gate_logits: jax.Array) -> jax.Array:
    """A looped decoder's exit distribution over its S passes, float32, from
    the gate's logits (S, ...): with ``lam_t = sigmoid(g_t)``, ``p_1 =
    lam_1``, ``p_t = lam_t prod_{j<t} (1 - lam_j)`` for 1 < t < S, and the
    last pass takes what is left, ``p_S = prod_{j<S} (1 - lam_j)``; the last
    gate's logit enters nothing. Sums to 1 over the passes."""
    g = gate_logits.astype(jnp.float32)[:-1]
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)  # log prod_{j<=t}(1-lam_j)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
    return jnp.concatenate(
        [jnp.exp(jax.nn.log_sigmoid(g) + before), jnp.exp(stay[-1:])])


def expected_exit_loss(hidden: jax.Array, gate_logits: jax.Array,
                       head_kernel: jax.Array, targets: jax.Array,
                       beta: float):
    """A looped decoder's training objective: the mean over tokens of
    ``sum_t p_t nll_t - beta H(p)``, the expectation of the S passes' token
    losses under the gate's exit distribution ``p`` less ``beta`` times that
    distribution's entropy. hidden (S, B, T, D): each pass's final hidden
    state; gate_logits (S, B, T); head_kernel (D, V), shared by the passes;
    targets (B, T) int. Returns (loss, each pass's mean token loss (S,),
    each pass's exit mass (S,): its probability summed over the tokens).

    Each pass's head product and ``lm_token_nll`` run under
    ``jax.checkpoint``: a pass keeps its hidden state and its (B, T) losses
    for the backward, never its (B, T, V) logits, so the S logit arrays do
    not live together. Scopes: ``ut.head`` the head products, ``lm.loss``
    the token losses, ``ut.exit`` the distribution, expectation and
    entropy."""
    S, B, T, D = hidden.shape

    @jax.checkpoint
    def pass_nll(h):
        with jax.named_scope("ut.head"):
            # one (B T, D) x (D, V) product, float32 out: as HybridLM's head
            logits = jnp.dot(h.reshape(B * T, D), head_kernel,
                             preferred_element_type=jnp.float32
                             ).reshape(B, T, -1)
        with jax.named_scope("lm.loss"):
            return lm_token_nll(logits, targets)

    nll = jnp.stack([pass_nll(hidden[t]) for t in range(S)])
    with jax.named_scope("ut.exit"):
        p = exit_distribution(gate_logits)
        entropy = -(p * jnp.log(jnp.maximum(p, 1e-30))).sum(0)
        loss = ((p * nll).sum(0) - beta * entropy).mean()
        return loss, nll.mean((1, 2)), p.sum((1, 2))


def _bce_with_logits(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-cell numerically-stable BCE-with-logits (log-sigmoid form).
    The ONE implementation shared by the training loss and the per-sample
    eval path — any stability/semantics change lands in both."""
    z = logits.astype(jnp.float32)
    t = targets.astype(jnp.float32)
    return jnp.maximum(z, 0.0) - z * t + jnp.log1p(jnp.exp(-jnp.abs(z)))


def masked_sigmoid_bce(logits: jax.Array, targets: jax.Array,
                       mask: jax.Array) -> jax.Array:
    """Multi-label binary cross-entropy: sum(BCE * mask) / max(sum(mask), 1)
    over every (example, label) cell. logits/targets (..., L) with 0/1
    float targets (the CheXpert 14-finding contract — reference
    ``app/fedcv/medical_chest_xray_image_clf/data/chexpert/dataset.py:11``
    label_header; their trainer drives BCEWithLogitsLoss over it)."""
    per = _bce_with_logits(logits, targets)
    m = jnp.broadcast_to(_broadcast_mask(mask, per.ndim), per.shape)
    return (per * m).sum() / jnp.maximum(m.sum(), 1.0)


def masked_multilabel_accuracy(logits: jax.Array, targets: jax.Array,
                               mask: jax.Array):
    """Per-label binary accuracy at threshold 0.5 (logit > 0), riding the
    (num_correct, num_valid) plumbing; valid counts (example, label) cells."""
    pred = (logits > 0.0).astype(jnp.float32)
    t = targets.astype(jnp.float32)
    m = jnp.broadcast_to(_broadcast_mask(mask, t.ndim), t.shape)
    return ((pred == t) * m).sum(), m.sum()


def per_sample_metrics(out: jax.Array, y: jax.Array, mask: jax.Array,
                       loss_kind: str = "ce", tol: float = 0.5):
    """Per-SAMPLE (loss_sum, correct, valid) f32 vectors, shape (B,).

    The segmented per-client evaluator (``FedSimulator.local_test_on_all_
    clients``) needs per-sample values so one compiled pass over mixed-client
    batches can scatter-add each sample's stats into its owner client's
    accumulator. Reductions run over every trailing (e.g. per-token) axis,
    so ``sum(loss_sum)/sum(valid)`` over any grouping equals the masked_*
    aggregate over the same samples — per-client and global numbers agree
    with the reference's sum-of-per-sample-loss / num-samples semantics
    (``/root/reference/python/fedml/simulation/sp/fedavg/fedavg_api.py:233``).
    """
    axes = tuple(range(1, max(y.ndim, mask.ndim)))
    if loss_kind == "bce":
        per = _bce_with_logits(out, y)
        m = jnp.broadcast_to(_broadcast_mask(mask, per.ndim), per.shape)
        lbl_axes = tuple(range(1, per.ndim))
        hit = ((out > 0.0).astype(jnp.float32) == y.astype(jnp.float32))
        return ((per * m).sum(lbl_axes), (hit * m).sum(lbl_axes),
                m.sum(lbl_axes))
    if loss_kind == "mse":
        p = out.astype(jnp.float32)
        if p.ndim == y.ndim + 1 and p.shape[-1] == 1:
            p = p[..., 0]
        err = jnp.square(p - y.astype(jnp.float32))
        m = jnp.broadcast_to(_broadcast_mask(mask, err.ndim), err.shape)
        hit = (jnp.abs(p - y.astype(jnp.float32)) <= tol)
        return ((err * m).sum(axes), (hit * m).sum(axes), m.sum(axes))
    logz = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logz, y[..., None], axis=-1)[..., 0]
    m = jnp.broadcast_to(_broadcast_mask(mask, ll.ndim), ll.shape)
    pred = jnp.argmax(out, axis=-1)
    correct = ((pred == y) * m).sum(axes)
    return (-(ll * m).sum(axes), correct, m.sum(axes))


def masked_mse(preds: jax.Array, targets: jax.Array, mask: jax.Array) -> jax.Array:
    """Sum(sq err * mask) / max(sum(mask), 1) — regression tasks (FedGraphNN
    moleculenet property regression). preds (...,) or (..., 1)."""
    p = preds.astype(jnp.float32)
    if p.ndim == targets.ndim + 1 and p.shape[-1] == 1:
        p = p[..., 0]
    err = jnp.square(p - targets.astype(jnp.float32))
    m = jnp.broadcast_to(_broadcast_mask(mask, err.ndim), err.shape)
    return (err * m).sum() / jnp.maximum(m.sum(), 1.0)


def masked_within_tolerance(preds: jax.Array, targets: jax.Array,
                            mask: jax.Array, tol: float = 0.5):
    """Regression 'accuracy': count of predictions within ``tol`` of the
    target (so regression rides the same correct/valid metric plumbing)."""
    p = preds.astype(jnp.float32)
    if p.ndim == targets.ndim + 1 and p.shape[-1] == 1:
        p = p[..., 0]
    hit = (jnp.abs(p - targets.astype(jnp.float32)) <= tol)
    m = jnp.broadcast_to(_broadcast_mask(mask, hit.ndim), hit.shape)
    return (hit * m).sum(), m.sum()

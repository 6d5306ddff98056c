"""Mixture-of-Experts FFNs: the capacity-factor ``MoEBlock`` and the dropless
``RoutedExperts`` that holds a chip's share of a layer's experts.

**``MoEBlock``** (the older one).

Absent in the reference (like TP/SP, SURVEY.md §2.8); first-class here
because the ``expert`` mesh axis is part of the parallelism contract. Design:
top-1 gating with capacity factor; dispatch/combine are einsums against a
one-hot routing tensor, so the whole layer is dense linear algebra the MXU
likes; the stacked expert weights (E, D, H) shard over ``AXIS_EXPERT`` and
GSPMD turns the dispatch einsum into the all-to-all. Aux load-balancing loss
follows Shazeer et al. (fraction-routed x mean-gate dot product).

**``RoutedExperts``** (``dropless_moe``): top-k routing with no capacity, so
no token is dropped at any imbalance, and static shapes under ``jit``, so one
compiled program whatever the routing. The router (float32: sigmoid scores,
a selection bias that takes no gradient, top-k, normalised weights) runs
over all ``num_experts``; the layer is told which experts it holds,
``experts_held = (offset, count)``, and computes their part of the result:
what expert parallelism asks of it. The ``N * k`` assignments are sorted by
expert, strangers last; the tokens' rows are gathered in that order into a
buffer (of ``N * k`` rows where it must be: every assignment may land here;
of twice a uniform router's mean load where that holds them); grouped
products over the held experts (the bundled megablox ``gmm``, whose grid
ends with the last held row: work follows the rows held, not the buffer)
make the expert, in one of two forms: the gated ``W2 (silu(W1 x) * W3 x)``
(three products) or ``W2 relu(W1 x)^2`` (two, no ``W3``); the rows go back
by the inverse permutation and each token sums its k rows, weighted by its
normalised scores times the layer's scaling factor. ``RoutedExperts`` may
add a shared expert of the same form that every token passes through,
whole on every chip (scope ``shared_expert``): across the shares of a layer
it counts once. Gathers both ways, forward and backward (each
permutation's transpose is the other's gather): no scatter-add runs.

What moves the rows (PR 33): one Pallas kernel pair
(``ops/pallas/row_move.py``, behind ``_take_rows``), rows out of tokens and
tokens out of rows with the weighted sum folded in, each the other's
transpose. They copy a row by one DMA from indices they are handed, do work
by the rows held and read nothing for a stranger, so no array of ``N * k``
rows exists, forward or backward (XLA:TPU ran such row gathers at a fifth of
HBM's rate and the combine ran one over all ``N * k`` slots: PERF.md section
6). What XLA still does here: the reshape that makes a row one slab (a pad
before it where the row fills none: PR 37, a bf16 row of 2688), the
backward's scale and dot of the rows moved, and scalar gathers (indices,
weights, the count of rows placed). **The rule**, applied at trace time to
what the code sees and to nothing else (``_row_move_impl``): the kernels
where a row is whole 128-lane sublanes of bf16 or float32 and the layer
has at least ``row_move.MIN_ROWS`` tokens; XLA's gather (the same algorithm,
its own fallback) at a width with a lane to spare, and under that many
tokens, where a kernel's set-up buys nothing (a model's 8-token init, a toy
layer).
``fedml_moe_row_move_total{impl="pallas"|"xla", use="rows"|"tokens"}``
reports the choice, once a call site a trace, for layers of ``MIN_ROWS``
tokens and more. ``dropless_moe`` is jitted, so a model's equal layers, and
the passes a remat step makes of each, share one trace and one lowering of
the layer and of every kernel under it: set-up every start pays, cache or no
cache.
On one chip there is no exchange, and nothing stands in for the absent
chips.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..core.telemetry import get_registry
from .pallas import row_move


def _rank_queue(onehot: jax.Array, capacity: int, offset=0.0):
    """One choice-rank's capacity queue: (N, E) routing one-hot ->
    (dispatch slice (N, E, C), keep mask (N, E)). ``offset`` shifts queue
    positions (second choices append after first choices)."""
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot + offset * onehot
    keep = (pos < capacity) * onehot
    p = jnp.clip(pos.astype(jnp.int32), 0, capacity - 1)
    return jax.nn.one_hot(p, capacity) * keep[..., None], keep


def _balance_aux(first_onehot: jax.Array, probs: jax.Array,
                 num_experts: int) -> jax.Array:
    """Shazeer/GShard load-balance loss: E * <fraction routed, mean prob>."""
    return num_experts * jnp.sum(
        first_onehot.mean(axis=0) * probs.mean(axis=0))


def top1_routing(
    gate_logits: jax.Array, num_experts: int, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(B*T, E) logits -> (dispatch (N, E, C), combine (N, E, C), aux_loss).

    Tokens beyond an expert's capacity are dropped (standard top-1 MoE);
    position-in-expert computed with a cumulative sum, everything static-shape.
    """
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    expert_onehot = jax.nn.one_hot(jnp.argmax(probs, axis=-1), num_experts)
    dispatch, keep = _rank_queue(expert_onehot, capacity)
    gate = (probs * keep).sum(axis=-1, keepdims=True)            # (N, 1)
    combine = dispatch * gate[..., None]
    return dispatch, combine, _balance_aux(expert_onehot, probs, num_experts)


def top2_routing(
    gate_logits: jax.Array, num_experts: int, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-2 gating (GShard/Switch-v2 style): each token routes to its two
    highest-probability experts, gates renormalized over the kept pair,
    independent capacity queues per choice rank (second choices only use
    capacity left by first choices). Same (dispatch, combine, aux) contract
    as :func:`top1_routing` — everything stays static-shape einsum fodder.
    """
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    oh1 = jax.nn.one_hot(jnp.argmax(probs, axis=-1), num_experts)
    # second choice masked in LOGIT space: with saturated gates the masked
    # probs underflow to an all-zero row and argmax would phantom-route to
    # expert 0, wasting its capacity on zero-gate tokens
    masked_logits = jnp.where(oh1 > 0, -jnp.inf,
                              gate_logits.astype(jnp.float32))
    oh2 = jax.nn.one_hot(jnp.argmax(masked_logits, axis=-1), num_experts)

    # first choices fill the queues first; second choices append after
    d1, _ = _rank_queue(oh1, capacity)
    d2, _ = _rank_queue(oh2, capacity, offset=oh1.sum(axis=0, keepdims=True))
    dispatch = d1 + d2
    # gates renormalized over the two choices; d1/d2 already carry the
    # keep masks, so dropped slots contribute nothing
    g1 = (probs * oh1).sum(-1)
    g2 = (probs * oh2).sum(-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    combine = (d1 * (g1 / denom)[:, None, None]
               + d2 * (g2 / denom)[:, None, None])
    # aux balance loss on FIRST choices (GShard convention)
    return dispatch, combine, _balance_aux(oh1, probs, num_experts)


class MoEBlock(nn.Module):
    """MoE FFN (top-1 or top-2 routing). Input (B, T, D) ->
    ``(out (B, T, D), aux_loss scalar)``; stacked expert kernels
    (E, D, H)/(E, H, D) are the leaves to shard over ``AXIS_EXPERT``.
    Callers must add ``aux_weight * aux_loss`` (typically 1e-2) to their
    objective — without it the router has no balancing pressure and can
    collapse all tokens onto one expert."""

    num_experts: int = 8
    dim: int = 256
    hidden_mult: int = 4
    capacity_factor: float = 1.25
    top_k: int = 1
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        B, T, D = x.shape
        N = B * T
        E = self.num_experts
        H = self.dim * self.hidden_mult
        # top-2 sends ~2x the tokens through the queues
        C = max(1, int(self.capacity_factor * self.top_k * N / E))
        tokens = x.reshape(N, D)
        gate_logits = nn.Dense(E, use_bias=False, dtype=self.dtype, name="gate")(tokens)
        if self.top_k == 2:
            dispatch, combine, aux = top2_routing(gate_logits, E, C)
        elif self.top_k == 1:
            dispatch, combine, aux = top1_routing(gate_logits, E, C)
        else:
            raise ValueError(f"top_k must be 1 or 2, got {self.top_k}")

        w_in = self.param("w_in", nn.initializers.lecun_normal(), (E, D, H), self.dtype)
        w_out = self.param("w_out", nn.initializers.lecun_normal(), (E, H, D), self.dtype)
        # dispatch: (N, E, C) x (N, D) -> (E, C, D); per-expert FFN; combine back
        expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(self.dtype), tokens)
        hidden = jax.nn.gelu(jnp.einsum("ecd,edh->ech", expert_in, w_in))
        expert_out = jnp.einsum("ech,ehd->ecd", hidden, w_out)
        out = jnp.einsum("nec,ecd->nd", combine.astype(self.dtype), expert_out)
        return out.reshape(B, T, D), aux


EXPERT_STACKED_LEAVES = ("w_in", "w_out")


def expert_param_shardings(mesh, params):
    """NamedShardings for a ``MoEBlock`` param tree on a mesh with an
    ``AXIS_EXPERT`` axis: the stacked expert kernels shard over the expert
    axis, everything else (gate, norms) replicates. The ONE place the
    expert-stacked leaf names live — used by the EP dryrun plane and the
    expert-parallel tests alike."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import AXIS_EXPERT

    def spec_for(path, leaf):
        names = [str(getattr(p, "key", p)) for p in path]
        which = (P(AXIS_EXPERT) if names[-1] in EXPERT_STACKED_LEAVES
                 else P())
        return NamedSharding(mesh, which)

    return jax.tree_util.tree_map_with_path(spec_for, params)


# --- dropless routed experts: a chip's share of a layer ---------------------

# (held, all, largest load of a held expert, dropped) assignments of a call
MOE_STATS = ("held", "total", "held_load_max", "dropped")
# gmm's (rows, k, n) tile: see ``_gmm_tiling``
GMM_TILE = (256, 2048, 768)
# (k, n) -> the tile at widths that are no multiples of ``GMM_TILE``, fitted on
# the chip over the product and its two transposes, which megablox hands the
# same tile (PERF.md section 6 has the table of what was tried): as deep as k
# rounded up to whole lanes, so that an expert's weight block stays in VMEM,
# and 384 columns, which divides 2688 and pads 1856 by 3%
GMM_TILE_AT = {(2688, 1856): (256, 2688, 384), (1856, 2688): (256, 1920, 384)}
# the usual row buffer, over the mean load of a uniform router
BUFFER_OVER_MEAN = 2


def _gmm_tiling(rows: int, k: int, n: int) -> Tuple[int, int, int]:
    """The grouped product's tile, cut to the problem: a tile as deep as k
    keeps an expert's weight block in VMEM while its rows stream past."""
    if (k, n) in GMM_TILE_AT:  # whole: its k tile may pad k up to whole lanes
        tm, tk, tn = GMM_TILE_AT[(k, n)]
        return min(tm, rows), tk, tn
    tm, tk, tn = GMM_TILE
    return min(tm, rows), min(tk, k), min(tn, n)


def _moves(order, place, n_held, rows: int, impl):
    """What the two row moves of a buffer of ``rows`` rows go by. ``order``
    (rows,): the slot (token * k + j) a buffer row belongs to; ``place``
    (N, k): the row a slot reads back, a stranger's clamped to the spare
    last row; ``held`` (N, k): the slots that have a row of their own in
    this buffer; and, for the kernel, those slots listed in token order."""
    N, k = place.shape
    moves = {"order": order, "n_held": n_held,
             "held": place < jnp.minimum(n_held, rows),
             "place": jnp.minimum(place, rows - 1)}
    if impl == "pallas":
        moves["lists"] = row_move.token_lists(order, n_held, N, k)
    return moves


def _row_move_impl(x) -> Optional[str]:
    """What moves the rows of a layer whose tokens are ``x`` (N, D), chosen
    at trace time from what the code sees and nothing else: ``"pallas"``,
    the kernels of ``ops/pallas/row_move.py``, where a row is whole
    128-lane sublanes; ``"xla"``, XLA's gather, where it is not; None, the
    same gather, under ``MIN_ROWS`` tokens (a model's 8-token init, a toy
    layer), where a kernel's set-up buys nothing and there is nothing to
    report."""
    N, D = x.shape
    if N < row_move.MIN_ROWS:
        return None
    return "pallas" if row_move.row_move_shapes_ok(D, x.dtype) else "xla"


def _count(use: str, impl) -> None:
    """``fedml_moe_row_move_total{impl, use}``: once a call site a trace,
    as ``fedml_attention_dispatch_total`` is counted."""
    if impl:
        get_registry().counter("fedml_moe_row_move_total", impl=impl,
                               use=use).inc()


def _rows_of_tokens(impl, src, moves):
    """Row r its token's row of ``src`` (N, D), bit for bit, over the rows
    held; zero rows after: (rows, D)."""
    _count("rows", impl)
    index = moves["order"] // moves["place"].shape[1]
    if impl == "pallas":
        return row_move.rows_from_tokens(src, index, moves["n_held"])
    held = (jnp.arange(index.shape[0]) < moves["n_held"])[:, None]
    return jnp.where(held, src[index], 0)


def _tokens_of_rows(impl, src, moves, w=None):
    """Each token's ``sum_j w[n, j] * src[place[n, j]]`` over its held
    slots (``w`` absent: ones), in float32, rounded once: ``src`` (rows, D)
    -> (N, D)."""
    _count("tokens", impl)
    held = moves["held"]
    w = held.astype(jnp.float32) if w is None else jnp.where(held, w, 0)
    if impl == "pallas":
        return row_move.tokens_from_rows(src, moves["lists"], w)
    taken = jnp.where(held[..., None], src[moves["place"]], 0)
    return jnp.einsum("nkd,nk->nd", taken, w,
                      preferred_element_type=jnp.float32).astype(src.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _take_rows(use: str, impl, src, w, moves):
    """The expert layer's two row moves (``moves``: ``_moves``; ``impl``:
    ``_row_move_impl``), each the other's transpose, so that both run as
    gathers forward and backward and no scatter-add runs. ``use ==
    "rows"``: the tokens' rows (N, D) into the buffer, ``_rows_of_tokens``
    (``w`` is None). ``use == "tokens"``: the buffer's rows back, each
    token its k weighted: ``_tokens_of_rows``."""
    return _take_rows_fwd(use, impl, src, w, moves)[0]


def _take_rows_fwd(use, impl, src, w, moves):
    if use == "rows":
        return _rows_of_tokens(impl, src, moves), moves
    return _tokens_of_rows(impl, src, moves, w), (src, w, moves)


def _take_rows_bwd(use, impl, res, g):
    # (the caller's scope does not reach a backward body: named here, so
    # that the benchmark's readers find the shuffle's time both ways)
    if use == "rows":
        with jax.named_scope("moe.shuffle.dispatch"):
            return _tokens_of_rows(impl, g, res), None, None
    src, w, moves = res
    with jax.named_scope("moe.shuffle.combine"):
        # a row's cotangent is its token's, weighted; the weight's is the
        # row against its token's cotangent, read back slot by slot (a
        # stranger's slot reads the spare row, whatever lies there, and
        # keeps none of it)
        taken = _rows_of_tokens(impl, g, moves).astype(jnp.float32)
        scale = w.reshape(-1)[moves["order"]][:, None]
        dots = jnp.sum(taken * src.astype(jnp.float32), axis=-1)
        g_w = jnp.where(moves["held"], dots[moves["place"]], 0)
    return (taken * scale).astype(src.dtype), g_w.astype(w.dtype), None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def route_top_k(x, gate, bias, top_k: int, router: str = "sigmoid"):
    """(N, D) tokens -> (chosen experts (N, k) int32, their weights (N, k)
    float32). Float32 throughout. ``router`` ``"sigmoid"``: ``s =
    sigmoid(x gate)``; the choice is ``top_k(s + bias)`` (the bias steers
    the choice only and takes no gradient; zeros are no bias); the weights
    are ``s`` at the chosen, over their sum + 1e-6. ``"softmax"``: ``p =
    softmax(x gate)`` over all the experts, the choice ``top_k(p)``, the
    weights ``p`` at the chosen over their sum; no bias (None)."""
    with jax.named_scope("moe.shuffle.route"):
        logits = jnp.dot(
            x.astype(jnp.float32), gate.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        if router == "softmax":
            w, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
            return chosen.astype(jnp.int32), w / w.sum(axis=-1, keepdims=True)
        s = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(
            s + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        return (chosen.astype(jnp.int32),
                w / (w.sum(axis=-1, keepdims=True) + 1e-6))


def _one_mesh_context(f):
    """``f`` under the abstract mesh there is, set by value. jax keys a
    jitted function's trace on the ambient mesh context, and where no mesh
    is set the first trace of a body sees none while every later pass over
    its jaxpr (the jvp, the transposes) sees the empty mesh: equal in
    meaning, another key, so the launchers and ``gmm`` under the layer were
    traced once a pass (PR 37: four traces of the tokens kernel's body for
    two sizes, 0.15 s each on a CPU core). Inside, all passes share one."""
    @functools.wraps(f)
    def in_context(*args, **kwargs):
        with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
            return f(*args, **kwargs)
    return in_context


@functools.partial(jax.jit, static_argnames=(
    "top_k", "experts_held", "scale", "router", "form"))
@_one_mesh_context
def dropless_moe(x, gate, bias, w1, w3, w2, *, top_k: int,
                 experts_held: Tuple[int, int], scale: float = 1.0,
                 router: str = "sigmoid", form: str = "swiglu", rx=None):
    """The held experts' part of a routed expert layer. x: (N, D); gate: (D,
    E) over all E experts; bias: (E,), or None for the softmax ``router``
    (``route_top_k``); w1, w3: (held, D, F); w2: (held, F, D), the experts
    ``offset .. offset + held - 1``. An expert is of ``form``
    (``EXPERT_FORMS``): ``W2 (act(W1 x) * W3 x)``, or for ``"relu2"``, with
    ``w3`` None, ``W2 relu(W1 x)^2``; a token's k rows are weighed by its
    normalised scores times ``scale``. The router reads ``rx`` (N, D) where
    given, else x.
    Returns ((N, D), stats (4,) int32 as MOE_STATS names them).

    The row buffer has a static size, and gathers and elementwise passes
    cost by the buffer, not by the rows in it. So there are two sizes in
    the one program, chosen by the count of rows held: ``BUFFER_OVER_MEAN``
    x the mean load of a uniform router where that holds them, else all
    ``N * k`` (every assignment may land here; nothing is ever dropped:
    ``dropped`` counts the held assignments that the size taken did not
    give a row of their own)."""
    N, D = x.shape
    offset, count = experts_held
    gated = EXPERT_FORMS[form] is not None
    if gated != (w3 is not None):
        raise ValueError(f"a {form} expert takes {'a' if gated else 'no'} "
                         "second product (w3)")
    chosen, w = route_top_k(x if rx is None else rx, gate, bias, top_k,
                            router)
    if scale != 1.0:
        w = w * scale
    A = N * top_k
    tm = _gmm_tiling(A, D, D)[0]
    # whole row tiles, and one spare: a stranger's clamped row index then
    # reads a row that is masked, never a held one
    buffer_of = lambda cap: (-(-cap // tm) + 1) * tm  # noqa: E731
    usual = BUFFER_OVER_MEAN * A * count // gate.shape[1]
    with jax.named_scope("moe.shuffle.dispatch"):
        group_sizes = jnp.bincount(
            chosen.reshape(A), length=gate.shape[1]).astype(jnp.int32)[
                offset:offset + count]
        n_held = group_sizes.sum()
        local = chosen.reshape(A) - offset
        local = jnp.where((local >= 0) & (local < count), local, count)
        order = jnp.argsort(local, stable=True)   # buffer row -> assignment
        place = jnp.argsort(order)                # assignment -> buffer row
        order = jnp.pad(order, (0, buffer_of(A) - A))
    held_part = functools.partial(
        _held_rows, order=order, place=place, is_held=local < count,
        group_sizes=group_sizes, n_held=n_held, top_k=top_k, form=form)
    if buffer_of(usual) >= buffer_of(A):
        out, placed = held_part(x, w, w1, w3, w2, rows=buffer_of(A))
    else:
        # each size saves only its inputs for the backward pass: a cond's
        # two sides would otherwise both hand back their residuals, the
        # side not taken as zeros of the large buffers' sizes
        out, placed = jax.lax.cond(
            n_held <= usual,
            jax.checkpoint(functools.partial(held_part, rows=buffer_of(usual))),
            jax.checkpoint(functools.partial(held_part, rows=buffer_of(A))),
            x, w, w1, w3, w2)
    stats = jnp.stack([n_held, jnp.int32(A), group_sizes.max(),
                       n_held - placed])
    return out, stats.astype(jnp.int32)


def _held_rows(x, w, w1, w3, w2, *, order, place, is_held, group_sizes,
               n_held, top_k: int, rows: int, form: str = "swiglu"):
    """The held experts over a buffer of ``rows`` rows (at least ``n_held +
    1``): the tokens' rows gathered in expert order, the grouped products
    (three, or two with ``w3`` None), the rows taken back and each token's
    k weighted.
    Returns ((N, D), the count of held assignments that found, at the row
    they read back, a computed row of their own: ``n_held`` unless the
    buffer was too small for them)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    N, D = x.shape
    interpret = jax.default_backend() != "tpu"
    valid = (jnp.arange(rows) < n_held)[:, None]
    order = order[:rows]
    with jax.named_scope("moe.shuffle.dispatch"):
        impl = _row_move_impl(x)
        moves = _moves(order, place.reshape(N, top_k), n_held, rows, impl)
        place = moves["place"].reshape(-1)  # strangers: the spare last row
        placed = jnp.sum(is_held & valid[place, 0]
                         & (order[place] == jnp.arange(N * top_k)),
                         dtype=jnp.int32)
        # rows past n_held come back zero, and their gradient (gmm leaves
        # it unwritten) is never read
        xs = _take_rows("rows", impl, x, None, moves)
    with jax.named_scope("moe.experts"):
        product = lambda a, b: gmm(  # noqa: E731
            a, b, group_sizes, a.dtype,
            _gmm_tiling(min(rows, N * top_k), b.shape[1], b.shape[2]), None,
            None, False, interpret)  # (the row tile the buffer was cut to)
        act = _hidden(form, product(xs, w1.astype(x.dtype)),
                      lambda: product(xs, w3.astype(x.dtype)))
        ys = product(jnp.where(valid, act, 0), w2.astype(x.dtype))
    with jax.named_scope("moe.shuffle.combine"):
        out = _take_rows("tokens", impl, ys, w, moves)
    return out, placed


# an expert's form -> the activation of its first product, which gates the
# second; None: ``relu²`` of the first, and no second
EXPERT_FORMS = {"swiglu": jax.nn.silu, "reglu": jax.nn.relu, "relu2": None}
ROUTERS = ("sigmoid", "softmax")


def _hidden(form: str, up, second):
    """An expert's hidden rows from its first product ``up``: ``act(up) *
    second()`` for a gated form, ``relu(up)^2`` for ``"relu2"``."""
    act = EXPERT_FORMS[form]
    return jnp.square(jax.nn.relu(up)) if act is None else act(up) * second()


class RoutedExperts(nn.Module):
    """A chip's share of a dropless top-k expert layer (``dropless_moe``).
    (B, T, D) -> ((B, T, D), stats (4,) int32). ``num_experts`` is the
    router's width; ``experts_held = (offset, count)`` the experts whose
    weights live here (all of them by default). ``form`` is an expert's:
    ``"swiglu"`` or ``"reglu"`` (``w1``, ``w3``, ``w2``: silu or relu of the
    first product, times the second) or ``"relu2"`` (``w1``, ``w2``);
    ``scale`` multiplies the routed sum; ``shared_width`` > 0 adds a shared
    expert of the same form and that width, which every token passes
    through: computed here whole, whatever the share (scope
    ``shared_expert``). ``router`` is ``route_top_k``'s. The sigmoid
    router's selection bias lives in the collection ``buffers``, outside
    the optimizer: the layer reads it and never moves it (how it is
    balanced is the training recipe's); the softmax router has none. The
    router reads ``router_input`` (B, T, D) where the call gives one (a
    router placed before the layer's attention), else ``x``. Under a mesh
    with a data axis each device routes its own rows (the grouped product
    is a Mosaic kernel GSPMD cannot partition): counts are summed over the
    devices, the largest load is the largest on any."""

    dim: int
    width: int
    num_experts: int
    top_k: int
    experts_held: Optional[Tuple[int, int]] = None
    dtype: jnp.dtype = jnp.float32
    mesh: Optional[object] = None
    form: str = "swiglu"
    scale: float = 1.0
    shared_width: int = 0
    router: str = "sigmoid"

    @nn.compact
    def __call__(self, x, router_input=None):
        B, T, D = x.shape
        offset, count = self.experts_held or (0, self.num_experts)
        if not 0 <= offset <= offset + count <= self.num_experts:
            raise ValueError(
                f"experts_held {(offset, count)} lies outside the router's "
                f"{self.num_experts} experts")
        if self.form not in EXPERT_FORMS or self.router not in ROUTERS:
            raise ValueError(
                f"an expert's form is one of {sorted(EXPERT_FORMS)} and its "
                f"router one of {ROUTERS}, not {self.form!r}, {self.router!r}")
        gated = EXPERT_FORMS[self.form] is not None
        init = nn.initializers.normal(0.02)
        gate = self.param("gate", init, (D, self.num_experts), jnp.float32)
        bias = None if self.router == "softmax" else self.variable(
            "buffers", "expert_bias", jnp.zeros, (self.num_experts,),
            jnp.float32).value
        w1 = self.param("w1", init, (count, D, self.width), jnp.float32)
        w3 = (self.param("w3", init, (count, D, self.width), jnp.float32)
              if gated else None)
        w2 = self.param("w2", init, (count, self.width, D), jnp.float32)

        def held_part(x, rx, gate, bias, w1, w3, w2, data_axis=None):
            kw = {} if rx is None else {"rx": rx.reshape(-1, D)}
            out, stats = dropless_moe(
                x.reshape(-1, D).astype(self.dtype), gate, bias, w1, w3, w2,
                top_k=self.top_k, experts_held=(offset, count),
                scale=self.scale, router=self.router, form=self.form, **kw)
            if data_axis:
                sums = jax.lax.psum(stats, data_axis)
                stats = sums.at[2].set(jax.lax.pmax(stats[2], data_axis))
            return out.reshape(x.shape), stats

        from ..parallel.mesh import AXIS_DATA

        mesh = self.mesh
        dp = mesh.shape.get(AXIS_DATA, 1) if mesh is not None else 1
        if dp > 1 and B % dp == 0:  # (a model's one-row init stays whole)
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            rep = P()
            held_part = shard_map(
                functools.partial(held_part, data_axis=AXIS_DATA), mesh=mesh,
                in_specs=(P(AXIS_DATA), P(AXIS_DATA), rep, rep, rep, rep,
                          rep),
                out_specs=(P(AXIS_DATA), rep), check_vma=False)
        if not self.shared_width:
            return held_part(x, router_input, gate, bias, w1, w3, w2)
        # (plain matmuls that GSPMD partitions by itself, outside the routed
        # part's ``shard_map``; not a method of the module: flax would put
        # ``moe._shared_expert`` in every op's name, and the routed experts'
        # scopes are found by ``moe.``)
        shared = lambda name, *shape: self.param(  # noqa: E731
            "shared_" + name, init, shape, jnp.float32).astype(self.dtype)
        D, F = x.shape[-1], self.shared_width
        always = _shared_expert(
            x.astype(self.dtype), shared("w1", D, F),
            shared("w3", D, F) if gated else None, shared("w2", F, D),
            self.form)
        out, stats = held_part(x, router_input, gate, bias, w1, w3, w2)
        return always + out, stats


def _shared_expert(x, w1, w3, w2, form):
    """The expert every token passes through, of the routed experts'
    ``form``."""
    with jax.named_scope("shared_expert"):
        return _hidden(form, x @ w1, lambda: x @ w3) @ w2

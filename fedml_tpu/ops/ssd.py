"""The Mamba-2 mixer's core: what lies between its two projections.

``[z, xBC, dt] = split(u W_in)``. Three parts, each under a scope of its own
so that a device trace can name its time:

- ``ssd.conv``: ``xBC = silu(conv(xBC) + b)``, a depthwise causal
  convolution over the last ``taps`` positions (tap ``j`` weighs the
  position ``j`` back, ``xBC_{<0} = 0``), as shifted adds XLA fuses.
- ``ssd.core``: the state-space scan. ``[x, B, C] = split(xBC)``, ``x`` as
  (heads, P), ``B`` and ``C`` as (groups, N), head ``h`` reading group ``h //
  (heads / groups)``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``,
  float32; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`` (P x N),
  ``y_t = S_t C_t + D x_t``.
- ``ssd.gate_norm``: ``RMSNorm_groups(y * silu(z)) * w``, the norm over each
  group's channels (the gate before the norm), float32.

**The scan's three forms.** ``ssd_scan`` chooses at trace time, from what
it sees in its input and nothing else, and counts both choices once a call
site a trace: the algorithm by the length and the chunk
(``fedml_ssd_dispatch_total{impl="chunked"|"sequential", seq_len, chunk}``),
what runs it by the shapes (``scan_kernel``:
``fedml_ssd_kernel_total{impl="pallas"|"xla", seq_len, chunk}``).

- ``"chunked"``, T a multiple of the chunk L: the state-space dual. With
  ``a_t = dt_t A`` and ``cum`` its running sum inside a chunk, a chunk's
  output is ``(mask(exp(cum_l - cum_s)) * C_l.B_s) (dt_s x_s)`` from its own
  positions plus ``exp(cum_l) C_l . S_in`` from the state it was handed; its
  closing state is ``sum_s exp(cum_L - cum_s) dt_s x_s (x) B_s``, and the
  states are carried over the T / L chunks (``S_in' = exp(cum_L) S_in +
  closing``). Decays and running sums in float32 (every exponent is <= 0, so
  nothing overflows); the four products (``C B^T``, the masked scores by
  ``x``, the closing state, ``C`` by the state handed in) take operands in
  the inputs' dtype and accumulate float32. Two things run it:

  - ``"pallas"``: the kernel pair of ``ops/pallas/ssd.py`` behind a custom
    VJP, where the shapes fill its tiles (the chunk and the state multiples
    of 128, a group's heads whole 128-lane tiles, two chunks or more: the
    Nemotron cell's 128 / 128 / 8 x 64). The (L, L) decays and masked
    scores, the closing states and the states handed on stay in VMEM; only
    the running sum (and, backward, its reverse) is XLA's. Imported here
    only where a scan takes it.
  - ``"xla"``: ``_chunked``, plain ``jax.numpy`` that XLA compiles and
    autodiff differentiates, with a ``lax.scan`` over the chunks; every
    shape the kernels' rule refuses (a chunk of 8, a state of 16).
- ``"sequential"``, T under one chunk (a model's 8-token init): the
  recurrence itself, ``lax.scan`` over t in float32 (``"xla"``).

A T of one chunk or more that is no multiple of the chunk is refused: a
padded tail would be silent work.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.telemetry import get_registry
from .short_conv import causal_taps


def causal_conv_silu(xBC: jax.Array, weight: jax.Array,
                     bias: jax.Array) -> jax.Array:
    """xBC: (B, T, C); weight: (C, taps), tap ``j`` weighs the position ``j``
    back; bias: (C,). Returns ``silu(conv(xBC) + bias)``, (B, T, C)."""
    with jax.named_scope("ssd.conv"):
        return jax.nn.silu(causal_taps(xBC, weight) + bias.astype(xBC.dtype))


def scan_impl(seq_len: int, chunk: int) -> str:
    """``"chunked"`` or ``"sequential"``, from the length and the chunk
    alone; a length of a chunk or more that is no multiple of it is
    refused."""
    if seq_len < chunk:
        return "sequential"
    if seq_len % chunk:
        raise ValueError(
            f"the chunked state-space scan needs the sequence length "
            f"({seq_len}) to be a multiple of the chunk ({chunk}): pad the "
            f"batch's rows to one, nothing here pads silently")
    return "chunked"


def scan_kernel(x, B, chunk: int) -> str:
    """What runs a scan of these arrays: ``"pallas"``, the kernel pair of
    ``ops/pallas/ssd.py``, where the chunked form's shapes fill its tiles
    (``kernel_shapes_ok``: the chunk and the state whole 128-lane tiles, a
    group's heads whole lane tiles, two chunks or more, a step's working
    set inside scoped VMEM), else ``"xla"``. From the shapes and the dtype
    alone."""
    (_, T, H, P), (G, N) = x.shape, B.shape[2:]
    if scan_impl(T, chunk) != "chunked":
        return "xla"
    from .pallas.ssd import kernel_shapes_ok
    return "pallas" if kernel_shapes_ok(
        T, chunk, H, P, G, N, jnp.dtype(x.dtype).itemsize) else "xla"


def ssd_scan(x, dt, A, B, C, D, *, chunk: int) -> jax.Array:
    """The scan of the module's docstring. x: (b, T, H, P); dt: (b, T, H),
    after its softplus, float32; A: (H,), negative, float32; B, C: (b, T, G,
    N) with G dividing H; D: (H,). Returns y: (b, T, H, P) in x's dtype."""
    b, T, H, _ = x.shape
    impl, kernel = scan_impl(T, chunk), scan_kernel(x, B, chunk)
    if isinstance(x, jax.core.Tracer):
        for family, by in (("fedml_ssd_dispatch_total", impl),
                           ("fedml_ssd_kernel_total", kernel)):
            get_registry().counter(family, impl=by, seq_len=T,
                                   chunk=chunk).inc()
    dt, A = dt.astype(jnp.float32), A.astype(jnp.float32)
    if kernel == "pallas":
        from .pallas.ssd import ssd_chunked
        cum = jnp.cumsum((dt * A).reshape(b, T // chunk, chunk, H), axis=2)
        return ssd_chunked(x, dt, cum.reshape(b, T, H), B, C, D, chunk)
    scan = _chunked if impl == "chunked" else _sequential
    y = scan(x, dt, A, B, C, chunk)
    skip = x.astype(jnp.float32) * D.astype(jnp.float32)[:, None]
    return (y + skip).astype(x.dtype)


def _sequential(x, dt, A, B, C, chunk: int):
    """The recurrence, one position at a time, float32. Returns float32."""
    del chunk
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    f32 = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)  # noqa: E731
    to_heads = lambda a: jnp.repeat(a, H // G, axis=1)  # noqa: E731  (b, H, N)

    def step(S, inputs):
        x_t, dt_t, B_t, C_t = inputs  # (b, H, P), (b, H), (b, G, N) twice
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * to_heads(B_t)[:, :, None])
        return S, jnp.einsum("bhpn,bhn->bhp", S, to_heads(C_t))

    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, N), jnp.float32),
                        (f32(x), f32(dt), f32(B), f32(C)))
    return jnp.moveaxis(y, 0, 1)


def _chunked(x, dt, A, B, C, chunk: int):
    """The dual form, a chunk of ``chunk`` positions at a time; the heads as
    (groups, heads a group) so that a group's ``B`` and ``C`` serve its
    heads without a copy. Returns float32."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    L, nc, Q = chunk, T // chunk, H // G
    dtype = x.dtype
    x = x.reshape(b, nc, L, G, Q, P)
    B = B.reshape(b, nc, L, G, N)
    C = C.reshape(b, nc, L, G, N)
    dt = dt.reshape(b, nc, L, G, Q)
    cum = jnp.cumsum(dt * A.reshape(G, Q), axis=2)  # (b, nc, L, G, Q), <= 0
    mm = lambda spec, p, q: jnp.einsum(  # noqa: E731
        spec, p.astype(dtype), q.astype(dtype),
        preferred_element_type=jnp.float32)

    # inside a chunk: position l reads positions s <= l of its own chunk
    scores = mm("bclgn,bcsgn->bcgls", C, B)  # a group's heads share them
    by_head = cum.transpose(0, 1, 3, 4, 2)  # (b, nc, G, Q, L)
    gap = by_head[..., :, None] - by_head[..., None, :]  # cum_l - cum_s
    keep = jnp.tril(jnp.ones((L, L), bool))  # (l, s): s <= l
    decay = jnp.exp(jnp.where(keep, gap, -jnp.inf))  # (b, nc, G, Q, l, s)
    xdt = x.astype(jnp.float32) * dt[..., None]  # dt_s x_s
    y = mm("bcgqls,bcsgqp->bclgqp", scores[:, :, :, None] * decay, xdt)

    # a chunk's closing state, and the states carried over the chunks
    to_end = jnp.exp(cum[:, :, -1:] - cum)  # (b, nc, L, G, Q)
    closing = mm("bcsgqp,bcsgn->bcgqpn", xdt * to_end[..., None], B)
    whole = jnp.exp(cum[:, :, -1])  # (b, nc, G, Q): a chunk's whole decay

    def carry(S, chunk_c):
        closing_c, whole_c = chunk_c
        return whole_c[..., None, None] * S + closing_c, S

    _, handed = jax.lax.scan(
        carry, jnp.zeros((b, G, Q, P, N), jnp.float32),
        (jnp.moveaxis(closing, 1, 0), jnp.moveaxis(whole, 1, 0)))
    handed = jnp.moveaxis(handed, 0, 1)  # (b, nc, G, Q, P, N): S entering c

    # what a chunk reads from the state it was handed
    y = y + mm("bclgn,bcgqpn->bclgqp", C, handed) * jnp.exp(cum)[..., None]
    return y.reshape(b, T, H, P)


def gated_rms_norm(y, z, weight, *, groups: int, eps: float) -> jax.Array:
    """``RMSNorm_groups(y * silu(z)) * weight``: y, z (B, T, C); the norm
    over each of ``groups`` runs of C / groups channels, in float32."""
    with jax.named_scope("ssd.gate_norm"):
        shape = y.shape
        g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        g = g.reshape(*shape[:-1], groups, shape[-1] // groups)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        return (g.reshape(shape) * weight.astype(jnp.float32)).astype(y.dtype)


def mamba2_core(zxbcdt, conv_weight, conv_bias, dt_bias, A_log, D, norm_weight,
                *, heads: int, head_dim: int, state: int, groups: int,
                chunk: int, eps: float) -> jax.Array:
    """(B, T, 2 inner + 2 groups state + heads), the input projection ->
    (B, T, inner), what the output projection takes; inner = heads x
    head_dim."""
    b, T, _ = zxbcdt.shape
    inner, bc = heads * head_dim, groups * state
    z, xBC, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc], axis=-1)
    xBC = causal_conv_silu(xBC, conv_weight, conv_bias)
    with jax.named_scope("ssd.core"):
        x, B, C = jnp.split(xBC, [inner, inner + bc], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + dt_bias.astype(jnp.float32))
        y = ssd_scan(x.reshape(b, T, heads, head_dim), dt,
                     -jnp.exp(A_log.astype(jnp.float32)),
                     B.reshape(b, T, groups, state),
                     C.reshape(b, T, groups, state), D, chunk=chunk)
    return gated_rms_norm(y.reshape(b, T, inner), z, norm_weight,
                          groups=groups, eps=eps)

#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, the normal entry points, no bench-only switch:

1. device    — jax.devices() must be a TPU; versions and the native .so
2. flagship  — fedml_tpu.init() + run_simulation() on the anchor config
               (FedAvg, CIFAR-10 stand-in, ResNet-56 bf16, 100 clients,
               10 a round, Dirichlet 0.5) for a few rounds with eval
3. lm        — DistributedLMTrainer at the README's LM flagship width
               (dim 1024, 12 layers, 16 heads, vocab 32000, bf16, AdamW)
               at T=4096 (attention dispatches to the flash kernel on a
               TPU from T=1024 up: PR 27's sweep, PERF.md section 6);
               then a small layer_types decoder (short convolution, grouped
               rotary attention, dropless routed experts on a share of the
               experts: models/hybrid_lm.py) through the same trainer, and
               that layer's row-move kernels against x[index] at the LFM2
               cell's shape (16,640 buffer rows for 65,536 slots, 2048 wide);
               a second small decoder of one-mixer blocks (Mamba-2, attention
               without positions, relu2 experts with a shared expert, an
               untied head), and the chunked state-space scan against the
               sequential one at the Nemotron cell's widths; a looped decoder
               at the Ouro cell's widths (two sandwich-norm layers of rotary
               attention 16 x 128 over SwiGLU 5632, applied four times, the
               exit gate and the objective over the passes)
4. kernels   — every other pallas_call against its in-repo reference:
               fused_gram (alone at a 1000-row cohort, and inside
               fused_sanitize_krum on a flagship cohort of ResNet-56
               updates), fused_quantize_pack q8/q4, conv2d_pallas
5. four chips (only when jax sees >= 4, and then FIRST, while every
               device's peak-memory counter is still untouched): stage 2
               with backend="TPU" (the client mesh over every device) and
               stage 3 with dp=2 x tp=2

Any exception, NaN or failed check ends the run with a non-zero code; the
last line of stdout is the result JSON only when every stage passed. There
is no CPU fallback and no switch that lets this pass off the chip: without
a TPU it exits 1 naming the platform it found.

The stage functions take their sizes as arguments so tests/test_chip_smoke.py
can shake them out at a tiny size on the CPU; ``main`` is the only caller
that passes the full sizes, and the only place the chip-only facts (platform,
compiled Mosaic calls) are required.
"""

from __future__ import annotations

import json
import logging
import sys
import time

FLAGSHIP = dict(
    dataset="cifar10", model="resnet56", partition_method="hetero",
    partition_alpha=0.5, client_num_in_total=100, client_num_per_round=10,
    epochs=1, batch_size=64, use_bf16=True, learning_rate=0.01,
    random_seed=0,
)
FLAGSHIP_ROUNDS = 3           # eval runs at round 0 and at the last round
LM_MODEL = dict(vocab_size=32000, dim=1024, num_heads=16, num_layers=12)
LM_SEQ = 4096                 # long context; "flash" on a TPU from T=1024
FLASH_SPLIT_SEQ = 12288       # past what one backward kernel holds of dq in VMEM
# the SmallThinker cell's windowed attention: T, head width and band (at two
# heads: the dense path it is checked against holds the (T, T) scores)
FLASH_WINDOW = dict(seq=16384, heads=2, dh=128, window=4096)
LM_BATCH = 4                  # ~5.5 GB of the v5e's 16 GB (XLA's own estimate)
LM_STEPS = 4
# a small layer_types decoder: every operator of models/hybrid_lm.py once,
# 8 of 16 experts held, long enough for the flash kernels on the chip
HYBRID_LM = dict(
    vocab_size=4096, hidden_size=512, num_dense_layers=1,
    layer_types=("conv", "full_attention", "conv"), intermediate_size=1024,
    moe_intermediate_size=256, num_experts=16, num_experts_per_tok=4,
    experts_held=(4, 8), num_attention_heads=8, num_key_value_heads=2)
HYBRID_SEQ, HYBRID_BATCH = 1024, 2
# a second small decoder, of one-mixer blocks: every kind of
# models/hybrid_lm.py's MIXER_KINDS once and the Mamba-2 mixer twice, 8 of
# 16 relu2 experts held, a shared expert, an untied head
HYBRID_MIXERS = dict(
    vocab_size=4096, hidden_size=512, num_dense_layers=0,
    layer_types=("mamba", "moe", "mamba", "attention"), intermediate_size=256,
    moe_intermediate_size=256, num_experts=16, num_experts_per_tok=6,
    experts_held=(4, 8), num_attention_heads=8, num_key_value_heads=2,
    mamba_num_heads=16, mamba_head_dim=64, ssm_state_size=128, n_groups=8,
    chunk_size=128, moe_shared_expert_intermediate_size=512,
    routed_scaling_factor=2.5, mlp_hidden_act="relu2",
    tie_word_embeddings=False)
# a looped decoder at the Ouro cell's widths: two layers applied four times
# over one set of weights, an exit gate, the expected loss over the passes
LOOPED_LM = dict(
    vocab_size=4096, hidden_size=2048, num_dense_layers=2,
    layer_types=("full_attention", "full_attention"), intermediate_size=5632,
    moe_intermediate_size=0, num_experts=0, num_experts_per_tok=0,
    num_attention_heads=16, num_key_value_heads=16, head_dim=128,
    norm_eps=1e-6, tie_word_embeddings=False, qk_norm=False,
    sandwich_norm=True, total_ut_steps=4)
# the Nemotron cell's scan: (batch, T, heads, head width, groups, state, chunk)
SSD_SHAPE = (1, 8192, 64, 64, 8, 128, 128)
# the LFM2 cell's expert layer: 16,384 tokens x top-4 over 64 experts of
# which 8 are held, so 16,640 buffer rows for 65,536 slots; rows 2048 wide,
# a slab as they lie. And the Nemotron cell's: 8,192 x top-6 over 128, 8
# held, 6,400 rows for 49,152 slots; rows 2688 wide, in a padded slab
ROW_MOVES = ((16384, 4, 64, 8, 2048), (8192, 6, 128, 8, 2688))
GRAM_SHAPE = (1000, 4096)     # cohort rows x flattened update width
GRAM_REFUSED = (10, 1_000_000)  # wider than full-row tiles fit in VMEM
QUANT_SHAPE = (1000, 65536)
# ResNet-56's three stages at the flagship batch: (batch, height/width, chans)
CONV_STAGES = ((64, 32, 16), (64, 16, 32), (64, 8, 64))


class CheckFailed(AssertionError):
    """A stage's result is wrong (python -O does not strip this check)."""


def require(cond, message: str) -> None:
    if not cond:
        raise CheckFailed(message)


def rel_err(got, want) -> float:
    """||got - want|| / ||want|| in float64 on the host."""
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def mosaic_calls(fn, *args) -> int:
    """How many compiled Mosaic kernels the lowered program holds. Zero
    means the Pallas call ran interpreted or took a jnp reference."""
    import jax

    return jax.jit(fn).lower(*args).as_text().count("tpu_custom_call")


def memory_stats() -> list:
    """Per-device allocator counters. A backend that keeps none (the CPU)
    gives empty dicts; one that does must have both."""
    import jax

    out = []
    for d in jax.devices():
        ms = d.memory_stats()
        out.append({} if not ms else {
            k: int(ms[k]) for k in ("bytes_in_use", "peak_bytes_in_use")})
    return out


def compiles_since(seen: dict, t0: float) -> dict:
    """What compiling cost since ``t0`` (wall clock), from the program's own
    telemetry: programs XLA compiled and programs loaded from the persistent
    cache (``fedml_jax_programs_total``, less the counts in ``seen``), and
    from the ``jax.compile`` spans since ``t0`` the seconds in XLA
    (``xla_s``, with the count of compiles that took a second or more — the
    ones jax persists) and the seconds loading cached executables. A warm
    run shows ``xla_over_1s == 0``: nothing the cache could hold was
    compiled again; what remains are programs under jax's one-second
    persistence threshold. A compile is a span only inside an open span:
    ``main`` runs every stage inside one."""
    from fedml_tpu.core.telemetry import get_registry, get_tracer

    counters = get_registry().snapshot()["counters"]
    out = {source: counters.get(
        f"fedml_jax_programs_total{{source={source}}}", 0) - seen.get(source, 0)
        for source in ("compiled", "cache")}
    compiles = [s for s in get_tracer().finished_spans()
                if s["name"] == "jax.compile" and s["start"] >= t0]
    xla = [s["duration"] for s in compiles if not s["cached"]]
    out.update(xla_s=round(sum(xla), 2), xla_over_1s=sum(d >= 1.0 for d in xla),
               cache_load_s=round(sum(s["duration"] for s in compiles
                                      if s["cached"]), 2))
    return out


class LogTap(logging.Handler):
    """Collects the engine's own 'which path engaged' log lines."""

    def __init__(self, prefixes):
        super().__init__(level=logging.INFO)
        self.prefixes = tuple(prefixes)
        self.lines: list = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith(self.prefixes):
            self.lines.append(msg)


# --------------------------------------------------------------- stage 1

def stage_device() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    from fedml_tpu import native

    devices = jax.devices()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "native": "so" if native.get_lib() is not None else "numpy-fallback",
    }


# --------------------------------------------------------------- stage 2

def stage_flagship(config: dict, rounds: int, backend: str = "sp") -> dict:
    """init() + run_simulation(): finite falling train loss, finite test_acc."""
    import numpy as np

    import fedml_tpu

    tap = LogTap(("FedSimulator:", "SimulatorTPU:"))
    root = logging.getLogger()
    root.addHandler(tap)
    old_level = root.level
    root.setLevel(min(old_level or logging.INFO, logging.INFO))
    try:
        fedml_tpu.init(config=dict(
            config, comm_round=rounds, backend=backend,
            # evaluates at round 0 and at the last round only
            frequency_of_the_test=max(rounds, 1) * 1000))
        t0 = time.perf_counter()
        history = fedml_tpu.run_simulation()
        wall = time.perf_counter() - t0
    finally:
        root.removeHandler(tap)
        root.setLevel(old_level)
    losses = [float(r["train_loss"]) for r in history]
    accs = [float(r["test_acc"]) for r in history if "test_acc" in r]
    require(len(history) == rounds, f"ran {len(history)} of {rounds} rounds")
    require(bool(np.all(np.isfinite(losses))), f"train_loss not finite: {losses}")
    require(losses[-1] < losses[0], f"train_loss did not fall: {losses}")
    require(accs and bool(np.all(np.isfinite(accs))),
            f"no finite test_acc in {rounds} rounds: {accs}")
    # the simulator's one phase clock: named phases + host_other partition
    # each round's wall (float addition only: a few ulps of a round)
    off = [r["round"] for r in history
           if abs(sum(r["phases"].values()) - r["round_time"])
           > 1e-9 + 1e-6 * r["round_time"]]
    require(not off, f"phases do not sum to round_time in rounds {off}")
    return {
        "engaged": tap.lines, "train_loss": losses, "test_acc": accs,
        "run_s": round(wall, 2),
        # host time to dispatch each round. A round whose lane length is
        # new compiles a new program, and in a run this short every round
        # can: cold, these are compile times, not round times
        "dispatch_s": [round(float(r["dispatch_time"]), 2) for r in history],
        "memory": memory_stats(),
    }


# --------------------------------------------------------------- stage 3

def stage_lm(model: dict, seq: int, batch: int, steps: int,
             dp: int = 1, tp: int = 1) -> dict:
    """A few AdamW steps on one seeded batch: finite falling loss; plus how
    many Mosaic calls the step lowers to and where the params live."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.attention import auto_attention_impl
    from fedml_tpu.parallel.trainer import (
        DistributedLMTrainer,
        DistTrainConfig,
    )

    heads, dh = model["num_heads"], model["dim"] // model["num_heads"]
    impl = auto_attention_impl(batch // dp, heads // tp, seq, dh, itemsize=2)
    t0 = time.perf_counter()
    trainer = DistributedLMTrainer(
        DistTrainConfig(dp=dp, tp=tp), max_len=seq, dtype=jnp.bfloat16,
        seed=0, **model)
    build_s = time.perf_counter() - t0
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, model["vocab_size"], (batch, seq + 1),
                          dtype=np.int32)
    x, y = tokens[:, :-1], tokens[:, 1:]
    n_mosaic = trainer._train_step.lower(
        trainer.params, trainer.opt_state, trainer.constants,
        jax.ShapeDtypeStruct(x.shape, jnp.int32, sharding=trainer.batch_sharding),
        jax.ShapeDtypeStruct(y.shape, jnp.int32, sharding=trainer.batch_sharding),
    ).as_text().count("tpu_custom_call")
    losses, step_s = [], []
    for _ in range(steps):
        t = time.perf_counter()
        losses.append(trainer.step(x, y))   # float(loss): waits on the device
        step_s.append(time.perf_counter() - t)
    require(bool(np.all(np.isfinite(losses))), f"LM loss not finite: {losses}")
    require(losses[-1] < losses[0], f"LM loss did not fall: {losses}")
    leaves = jax.tree_util.tree_leaves(trainer.params)
    holders = set().union(*(leaf.sharding.device_set for leaf in leaves))
    biggest = max(leaves, key=lambda a: a.size)
    return {
        "attention_impl": impl, "mosaic_calls_lowered": n_mosaic,
        "mesh": dict(trainer.mesh.shape), "batch": batch, "seq": seq,
        "loss": [round(v, 4) for v in losses],
        "build_s": round(build_s, 2),
        "first_step_s": round(step_s[0], 2),      # compile + one step
        "steady_step_s": round(min(step_s[1:]), 3) if steps > 1 else None,
        "param_devices": len(holders),
        "largest_param_shard": list(
            biggest.addressable_shards[0].data.shape),
        "largest_param": list(biggest.shape),
        "memory": memory_stats(),
    }


def stage_hybrid_lm(model: dict, seq: int, batch: int, steps: int) -> dict:
    """A layer_types decoder (``HYBRID_LM``: short convolution, grouped
    rotary attention, dropless routed experts on a share of the experts;
    ``HYBRID_MIXERS``: the one-mixer blocks; ``LOOPED_LM``: a stack applied
    several times) through the trainer: finite falling loss, no assignment
    dropped, every pass counted, and how many Mosaic calls the step lowers
    to (flash forward and backward, the grouped products)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.core.telemetry import get_registry
    from fedml_tpu.models.hybrid_lm import DecoderConfig
    from fedml_tpu.ops.attention import auto_attention_impl
    from fedml_tpu.parallel.trainer import (
        DistributedLMTrainer,
        DistTrainConfig,
    )

    cfg = DecoderConfig(**model)
    heads = cfg.num_attention_heads
    impl = auto_attention_impl(
        batch, heads, seq, cfg.head_dim or cfg.hidden_size // heads, itemsize=2)
    trainer = DistributedLMTrainer(DistTrainConfig(), dtype=jnp.bfloat16,
                                   seed=0, model=cfg)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    x, y = tokens[:, :-1], tokens[:, 1:]
    spec = jax.ShapeDtypeStruct(x.shape, jnp.int32,
                                sharding=trainer.batch_sharding)
    n_mosaic = trainer._train_step.lower(
        trainer.params, trainer.opt_state, trainer.constants, spec, spec,
    ).as_text().count("tpu_custom_call")
    registry = get_registry()
    before = registry.counter_total("fedml_moe_assignments_total")
    passes_before = registry.counter_total("fedml_lm_ut_passes_total")
    losses = [trainer.step(x, y) for _ in range(steps)]
    require(bool(np.all(np.isfinite(losses))),
            f"hybrid LM loss not finite: {losses}")
    require(losses[-1] < losses[0], f"hybrid LM loss did not fall: {losses}")
    routed = registry.counter_total("fedml_moe_assignments_total") - before
    require(routed == steps * cfg.expert_layers * batch * seq
            * cfg.num_experts_per_tok,
            f"the expert layers counted {routed} assignments")
    dropped = registry.counter_total("fedml_moe_dropped_total")
    require(dropped == 0, f"{dropped} assignments were dropped")
    passes = (registry.counter_total("fedml_lm_ut_passes_total")
              - passes_before)
    require(passes == (steps * cfg.total_ut_steps if cfg.total_ut_steps > 1
                       else 0), f"the decoder counted {passes} passes")
    return {"attention_impl": impl, "mosaic_calls_lowered": n_mosaic,
            "batch": batch, "seq": seq, "loss": [round(v, 4) for v in losses],
            "assignments": int(routed), "memory": memory_stats()}


def check_ssd_vs_sequential(batch: int, seq: int, heads: int, head_dim: int,
                            groups: int, state: int, chunk: int) -> dict:
    """The state-space scan (ops/ssd.py) in bfloat16, as ``ssd_scan``
    dispatches it (the kernel pair of ops/pallas/ssd.py at the Nemotron
    cell's widths, XLA's chunked form at widths that fill no tile), against
    the sequential recurrence in float32 (written here), on seeded inputs
    with the steps and decays a seeded Mamba-2 mixer has (dt about 0.001 to
    0.1, A in -16..-1): values and the gradient of every input. Where the
    kernel was taken, XLA's chunked form is held to the same recurrence
    beside it, and both are timed (ms a call on the host's clock, the median
    of five: forward, and forward + backward).

    Tolerance 2e-2 of the largest element: x, B, C, the masked scores and
    the state handed to a chunk are each rounded to bfloat16 (2^-9 = 2e-3
    an operand) before a product that sums 128 terms in float32, and a
    position's output adds its own chunk's part and the carried state's;
    measured on the chip at the cell's widths (PERF.md section 6, PR 35),
    the kernels | XLA's chunked form: y 3.2e-3 | 2.7e-3, dx 3.0e-3 | 2.9e-3,
    ddt 3.7e-3 | 4.0e-3, dA 5.3e-3 | 5.3e-3, dB and dC 4.1e-3 | 4.1e-3, dD
    2.3e-3 | 2.3e-3. A scan that dropped its carried state reads 0.3 and more."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops import ssd

    rng = np.random.default_rng(5)
    n = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    x, B, C = n(batch, seq, heads, head_dim), n(batch, seq, groups, state), n(
        batch, seq, groups, state)
    dt = jnp.exp(jnp.asarray(rng.uniform(
        np.log(0.001), np.log(0.1), (batch, seq, heads)), jnp.float32))
    A = -jnp.asarray(rng.uniform(1, 16, heads), jnp.float32)
    D = jnp.ones(heads, jnp.float32)
    w = n(batch, seq, heads, head_dim)
    low = lambda a: a.astype(jnp.bfloat16)  # noqa: E731

    def recurrence(x, dt, A, B, C, D):
        """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t +
        D x_t``, one position at a time in float32; in blocks of ``chunk``
        steps under ``jax.checkpoint``, so that its backward pass keeps one
        state a block (every state of 8,192 positions is 16 GB)."""
        rep = heads // groups
        by_block = [jnp.moveaxis(a, 1, 0).reshape(-1, chunk, *a.shape[:1],
                                                  *a.shape[2:])
                    for a in (x, dt, jnp.repeat(B, rep, 2), jnp.repeat(C, rep, 2))]

        def step(S, at_t):
            x_t, dt_t, B_t, C_t = at_t
            S = (jnp.exp(dt_t * A)[..., None, None] * S
                 + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None])
            return S, jnp.sum(S * C_t[:, :, None], -1)

        _, y = jax.lax.scan(
            jax.checkpoint(lambda S, at: jax.lax.scan(step, S, at)),
            jnp.zeros((batch, heads, head_dim, state), jnp.float32), by_block)
        return jnp.moveaxis(y.reshape(-1, *y.shape[2:]), 0, 1) + x * D[:, None]

    def forward(scan, cast):
        return jax.jit(lambda x, dt, A, B, C, D: scan(
            cast(x), dt, A, cast(B), cast(C), D))

    def value_and_grads(scan, cast):
        def loss(x, dt, A, B, C, D):
            y = scan(cast(x), dt, A, cast(B), cast(C), D).astype(jnp.float32)
            return jnp.sum(y * w), y
        return jax.jit(jax.value_and_grad(loss, tuple(range(6)), has_aux=True))

    def ms(f) -> float:
        """A call's wall time, the median of five after the first."""
        jax.block_until_ready(f(x, dt, A, B, C, D))
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x, dt, A, B, C, D))
            times.append(time.perf_counter() - t0)
        return round(1e3 * float(np.median(times)), 3)

    def against_recurrence(scan) -> dict:
        (_, y), grads = value_and_grads(scan, low)(x, dt, A, B, C, D)
        return {name: float(f"{rel_err(g, t):.3e}") for name, g, t in zip(
            ("y", "dx", "ddt", "dA", "dB", "dC", "dD"), (y,) + grads, want)}

    def chunked_by_xla(x, dt, A, B, C, D):
        y = ssd._chunked(x, dt, A, B, C, chunk)
        return (y + x.astype(jnp.float32) * D[:, None]).astype(x.dtype)

    impl, kernel = ssd.scan_impl(seq, chunk), ssd.scan_kernel(low(x), B, chunk)
    dispatched = lambda *a: ssd.ssd_scan(*a, chunk=chunk)  # noqa: E731
    (_, y), grads = value_and_grads(recurrence, lambda a: a)(x, dt, A, B, C, D)
    want = (y,) + grads
    errs = against_recurrence(dispatched)
    require(all(np.isfinite(e) and e <= 2e-2 for e in errs.values()),
            f"the scan ({kernel}) vs the sequential recurrence: {errs}")
    out = {"impl": impl, "kernel": kernel, "chunk": chunk, "rel_err": errs,
           "shape": [batch, seq, heads, head_dim, groups, state]}
    if kernel == "pallas":
        out["rel_err_xla"] = against_recurrence(chunked_by_xla)
        out["ms_fwd"], out["ms_fwd_bwd"] = (
            ms(forward(dispatched, low)), ms(value_and_grads(dispatched, low)))
        out["ms_fwd_xla"], out["ms_fwd_bwd_xla"] = (
            ms(forward(chunked_by_xla, low)),
            ms(value_and_grads(chunked_by_xla, low)))
    return out


def check_flash_vs_dense(seq: int, heads: int, dh: int, batch: int = 1,
                         window=None) -> dict:
    """flash forward and backward against multihead_attention(impl='dense')
    on seeded bf16 inputs, at bf16 tolerance; with ``window``, both over
    that causal band."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.attention import multihead_attention

    rng = np.random.default_rng(1)
    q, k, v, w = (jnp.asarray(rng.standard_normal((batch, seq, heads, dh)),
                              jnp.bfloat16) for _ in range(4))

    def loss(impl):
        def f(q, k, v):
            out = multihead_attention(q, k, v, causal=True, impl=impl,
                                      window=window)
            return (out.astype(jnp.float32) * w.astype(jnp.float32)).sum(), out
        return f

    fwd_bwd = lambda impl: jax.jit(  # noqa: E731
        jax.value_and_grad(loss(impl), argnums=(0, 1, 2), has_aux=True))
    (_, out_f), g_f = fwd_bwd("flash")(q, k, v)
    (_, out_d), g_d = fwd_bwd("dense")(q, k, v)
    errs = {"out": rel_err(out_f, out_d)}
    for name, a, b in zip(("dq", "dk", "dv"), g_f, g_d):
        errs[name] = rel_err(a, b)
    for name, e in errs.items():
        require(np.isfinite(e) and e <= 2e-2,
                f"flash vs dense {name}: relative error {e:.3e} > 2e-2")
    return {
        "rel_err": {k_: float(f"{e:.3e}") for k_, e in errs.items()},
        "mosaic_calls_lowered": mosaic_calls(
            jax.grad(lambda q, k, v: loss("flash")(q, k, v)[0], (0, 1, 2)),
            q, k, v),
    }


# --------------------------------------------------------------- stage 4

def check_row_moves(tokens: int, top_k: int, experts: int, held: int,
                    width: int) -> dict:
    """The expert layer's two row moves (ops/pallas/row_move.py) against
    XLA's gather on seeded bf16 rows, routed as a balanced router would:
    ``held`` of ``experts`` experts' rows in a buffer of twice their mean
    load. The rows out are ``x[index]`` bit for bit and zero past the rows
    held; the rows back, each token's weighted sum in float32, at bf16
    tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.pallas.row_move import (
        rows_from_tokens,
        token_lists,
        tokens_from_rows,
    )

    rng = np.random.default_rng(3)
    slots = tokens * top_k
    rows = (-(-2 * slots * held // experts // 256) + 1) * 256
    chosen = jnp.asarray(np.argsort(rng.random((tokens, experts)))[:, :top_k]
                         .reshape(slots), jnp.int32)
    local = jnp.where(chosen < held, chosen, held)
    by_expert = jnp.argsort(local, stable=True).astype(jnp.int32)
    order = jnp.pad(by_expert, (0, max(0, rows - slots)))[:rows]
    place = jnp.argsort(by_expert).reshape(tokens, top_k)
    n_held = jnp.sum(chosen < held, dtype=jnp.int32)
    require(int(n_held) < rows, f"{int(n_held)} rows held of {rows}")
    x = jnp.asarray(rng.standard_normal((tokens, width)), jnp.bfloat16)
    ys = jnp.asarray(rng.standard_normal((rows, width)), jnp.bfloat16)
    w = jnp.where(place < n_held,
                  jnp.asarray(rng.random((tokens, top_k)), jnp.float32), 0)

    out = jax.jit(lambda x, o, n: rows_from_tokens(x, o // top_k, n))
    back = jax.jit(lambda ys, o, n, w: tokens_from_rows(
        ys, token_lists(o, n, tokens, top_k), w))
    got = out(x, order, n_held)
    want = jnp.where((jnp.arange(rows) < n_held)[:, None], x[order // top_k], 0)
    require(bool((got == want).all()),
            "rows_from_tokens differs from x[index] over the rows held")
    got = back(ys, order, n_held, w)
    want = jnp.einsum("nkd,nk->nd", ys[jnp.minimum(place, rows - 1)], w,
                      preferred_element_type=jnp.float32)
    err = rel_err(got, want)
    require(np.isfinite(err) and err <= 1e-2,
            f"tokens_from_rows vs the float32 sum: relative error {err:.3e}")
    return {"slots": slots, "rows": rows, "rows_held": int(n_held),
            "width": width, "rel_err_back": float(f"{err:.3e}"),
            "mosaic_calls_lowered": mosaic_calls(out, x, order, n_held)
            + mosaic_calls(back, ys, order, n_held, w)}


def check_gram(shape, interpret=None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.pallas import fused_gram
    from fedml_tpu.ops.pallas.agg_robust import _reference_gram

    flat = jnp.asarray(
        np.random.default_rng(2).standard_normal(shape), jnp.float32)
    kern = lambda x: fused_gram(x, interpret=interpret)  # noqa: E731
    t0 = time.perf_counter()
    got = jax.block_until_ready(jax.jit(kern)(flat))
    first_s = time.perf_counter() - t0
    want = jax.jit(_reference_gram)(flat)
    with jax.default_matmul_precision("highest"):
        want_hi = jax.jit(_reference_gram)(flat)
    err, err_hi = rel_err(got, want), rel_err(got, want_hi)
    require(got.shape == (shape[0], shape[0]), f"gram shape {got.shape}")
    # the MXU's default pass rounds f32 operands to bf16, in the kernel and
    # in XLA's reference alike; against the full-f32 reference that is the
    # error to expect, not a kernel defect
    require(np.isfinite(err_hi) and err_hi <= 1e-2,
            f"fused_gram vs f32 reference: relative error {err_hi:.3e}")
    return {"shape": list(shape), "rel_err_vs_reference": float(f"{err:.3e}"),
            "rel_err_vs_f32_reference": float(f"{err_hi:.3e}"),
            "mosaic_calls_lowered": mosaic_calls(kern, flat),
            "first_call_s": round(first_s, 2)}


def check_gram_refuses(shape, interpret=None) -> dict:
    """Past the width its full-row tiles hold, the compiled dispatch must
    refuse by type — not hand back the reference under the kernel's name."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.pallas import GramKernelShapeError, fused_gram

    try:
        jax.eval_shape(lambda x: fused_gram(x, interpret=interpret),
                       jax.ShapeDtypeStruct(shape, jnp.float32))
    except GramKernelShapeError as e:
        return {"shape": list(shape), "refused": str(e)}
    raise CheckFailed(f"fused_gram took a {shape} stack without the kernel "
                      "and without an error")


def model_param_shapes(config: dict, input_shape, classes: int):
    """The config's model's parameter pytree, shapes only."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    from fedml_tpu import models

    model = models.create(SimpleNamespace(**config), classes)
    return jax.eval_shape(lambda: models.init_params(
        model, jax.random.PRNGKey(0),
        jnp.zeros((1,) + tuple(input_shape), jnp.float32)))["params"]


def check_sanitize_krum(template, cohort: int, interpret=None) -> dict:
    """The agg_kernels defense path at a model's real width:
    fused_sanitize_krum (its Gram plane in the Pallas kernel) against the
    sanitize_stacked -> krum_aggregate pair the simulator runs without it,
    on a seeded cohort of updates shaped like ``template`` in which client
    1 uploads a NaN and client 2 a boosted update."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.core.robust import (
        fused_sanitize_krum,
        krum_aggregate,
        sanitize_stacked,
    )

    leaves, treedef = jax.tree_util.tree_flatten(template)
    rng = np.random.default_rng(5)
    stack = [(0.01 * rng.standard_normal((cohort,) + tuple(leaf.shape))
              ).astype(np.float32) for leaf in leaves]
    stack[0][1].flat[0] = np.nan
    for rows in stack:
        rows[2] *= 1e3
    updates = jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(rows) for rows in stack])
    weights = jnp.full((cohort,), 8.0, jnp.float32)
    n_byz, m = 2, 3

    def fused(u, w):
        return fused_sanitize_krum(u, w, n_byz=n_byz, m=m, use_kernel=True,
                                   interpret=interpret)

    def unfused(u, w):
        clean, cw, quar, z = sanitize_stacked(u, w)
        agg, sel = krum_aggregate(clean, cw, n_byz=n_byz, m=m)
        return agg, cw, quar, z, sel

    t0 = time.perf_counter()
    agg_f, _, quar_f, _, sel_f = jax.block_until_ready(
        jax.jit(fused)(updates, weights))
    first_s = time.perf_counter() - t0
    agg_u, _, quar_u, _, sel_u = jax.jit(unfused)(updates, weights)
    quarantined = np.flatnonzero(np.asarray(quar_f)).tolist()
    require(quarantined == [1, 2] and np.array_equal(quar_f, quar_u),
            f"quarantine: fused {quarantined}, unfused "
            f"{np.flatnonzero(np.asarray(quar_u)).tolist()}, planted [1, 2]")
    selected = np.flatnonzero(np.asarray(sel_f)).tolist()
    require(len(selected) == m and np.array_equal(sel_f, sel_u),
            f"Krum selection: fused {selected}, unfused "
            f"{np.flatnonzero(np.asarray(sel_u)).tolist()}")
    flatten = lambda t: np.concatenate(  # noqa: E731
        [np.asarray(x, np.float64).ravel()
         for x in jax.tree_util.tree_leaves(t)])
    err = rel_err(flatten(agg_f), flatten(agg_u))
    require(np.isfinite(err) and err <= 1e-6,
            f"fused vs unfused Krum aggregate: relative error {err:.3e}")
    return {"cohort": cohort,
            "width": int(sum(np.prod(leaf.shape) for leaf in leaves)),
            "quarantined": quarantined, "selected": selected,
            "agg_rel_err_vs_unfused": float(f"{err:.3e}"),
            "mosaic_calls_lowered": mosaic_calls(fused, updates, weights),
            "first_call_s": round(first_s, 2)}


def check_quant(shape, bits: int, interpret=None) -> dict:
    """Kernel == its jnp reference bit for bit on the device; and, since
    those two share their arithmetic, also == the two codecs written
    independently of it: the decode of the unfused XLA path over the whole
    stack, and the numpy wire codec byte for byte on every row."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.comm.codec import (
        _quant_roundtrip_jnp,
        pack_int4,
        stochastic_quantize,
    )
    from fedml_tpu.ops.pallas import fused_quantize_pack

    C, m = shape
    vals = np.random.default_rng(3 + bits).standard_normal(shape).astype(
        np.float32)
    vals[0, :300] = 0.0           # an all-zero chunk: the amax == 0 scale
    cids = np.arange(7, 7 + C, dtype=np.uint32)
    seed, rnd, leaf = 13, 2, 99

    def run(use_kernel):
        return lambda v, r, c: fused_quantize_pack(  # noqa: E731
            v, bits, seed, r, c, leaf, use_kernel=use_kernel,
            interpret=interpret if use_kernel else None)

    args = (jnp.asarray(vals), jnp.uint32(rnd), jnp.asarray(cids))
    t0 = time.perf_counter()
    got = jax.block_until_ready(jax.jit(run(True))(*args))
    first_s = time.perf_counter() - t0
    want = jax.jit(run(False))(*args)
    mismatched = {
        name: int(np.count_nonzero(np.asarray(a) != np.asarray(b)))
        for name, a, b in zip(("packed", "scales", "dec"), got, want)}
    require(not any(mismatched.values()),
            f"fused_quantize_pack q{bits} differs from its reference "
            f"(elements): {mismatched}")
    packed, scales, dec = (np.asarray(a) for a in got)
    unfused = np.asarray(jax.jit(
        lambda v, r, c: _quant_roundtrip_jnp(v, bits, seed, r, c, leaf, jnp)
    )(*args))
    n_off = int(np.count_nonzero(dec.view(np.uint32)
                                 != unfused.view(np.uint32)))
    require(n_off == 0, f"fused_quantize_pack q{bits}: {n_off} decoded "
            "elements differ from the unfused codec._quant_roundtrip_jnp")
    for row in range(C):
        q, s, d = stochastic_quantize(vals[row], bits, seed, rnd,
                                      int(cids[row]), leaf)
        wire = pack_int4(q) if bits == 4 else q
        require(np.array_equal(packed[row], wire)
                and np.array_equal(scales[row], s)
                and np.array_equal(dec[row], d),
                f"fused_quantize_pack q{bits} row {row} differs from the "
                "numpy wire codec")
    return {"shape": list(shape), "bits": bits, "bit_identical": True,
            "wire_rows_checked": C,
            "mosaic_calls_lowered": mosaic_calls(run(True), *args),
            "first_call_s": round(first_s, 2)}


def check_conv(stage, dtype_name: str = "bfloat16") -> dict:
    """conv2d_pallas forward and both gradients against XLA's conv."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.conv import conv2d_pallas

    b, hw, c = stage
    dtype = jnp.dtype(dtype_name)
    rng = np.random.default_rng(4 + hw)
    x = jnp.asarray(rng.standard_normal((b, hw, hw, c)), dtype)
    w = jnp.asarray(rng.standard_normal((3, 3, c, c)) * 0.1, dtype)
    cot = jnp.asarray(rng.standard_normal((b, hw, hw, c)), jnp.float32)

    def xla_conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def loss_of(conv):
        def loss(x, w):
            out = conv(x, w)
            return (out.astype(jnp.float32) * cot).sum(), out
        return loss

    def fwd_bwd(conv):
        return jax.jit(jax.value_and_grad(loss_of(conv), (0, 1), has_aux=True))

    pallas = lambda x, w: conv2d_pallas(x, w, 1, "SAME")  # noqa: E731
    t0 = time.perf_counter()
    (_, out_p), g_p = jax.block_until_ready(fwd_bwd(pallas)(x, w))
    first_s = time.perf_counter() - t0
    (_, out_x), g_x = fwd_bwd(xla_conv)(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    errs = {"out": rel_err(out_p, out_x), "dx": rel_err(g_p[0], g_x[0]),
            "dw": rel_err(g_p[1], g_x[1])}
    for name, e in errs.items():
        require(np.isfinite(e) and e <= tol,
                f"conv2d_pallas {stage} {name}: relative error {e:.3e} > {tol}")
    return {"stage": list(stage), "dtype": dtype_name,
            "rel_err": {k: float(f"{e:.3e}") for k, e in errs.items()},
            # forward, dx (the forward kernel again) and dw
            "mosaic_calls_lowered": mosaic_calls(
                jax.grad(loss_of(pallas), (0, 1), has_aux=True), x, w),
            "first_call_s": round(first_s, 2)}


# --------------------------------------------------------------- stage 5

# every device of a stage's mesh must raise its peak-memory counter by at
# least this much while the stage runs (the flagship replicates ~0.6 GB of
# training data per device; the LM shards ~1 GB of params + AdamW state)
MIN_PEAK_GROWTH = 128 << 20


def check_four_chips(before: list, fl: dict, lm: dict) -> dict:
    """The flagship over the client mesh (``fl``: stage 2 with
    backend="TPU") and the LM step with dp=2 x tp=2 (``lm``), run in that
    order, must each have put every device to work: each device's
    ``peak_bytes_in_use`` grows by ``MIN_PEAK_GROWTH`` or more from
    ``before`` (memory_stats() read before the two) to the flagship's
    reading, and again to the LM's. The counter only ever rises, so the
    two run before any one-chip stage has raised device 0's. A backend
    that keeps no such counter cannot pass (KeyError)."""
    n_dev = len(before)
    mesh_lines = [ln for ln in fl["engaged"] if ln.startswith("SimulatorTPU:")]
    require(mesh_lines and f"over {n_dev} of {n_dev} devices" in mesh_lines[0],
            f"flagship mesh leaves devices idle: {fl['engaged']}")
    peaks = [[m["peak_bytes_in_use"] for m in snap]
             for snap in (before, fl["memory"], lm["memory"])]
    for name, prev, cur in (("flagship", peaks[0], peaks[1]),
                            ("lm", peaks[1], peaks[2])):
        require(len(cur) == n_dev, f"{name}: {len(cur)} devices reported")
        idle = [i for i in range(n_dev)
                if cur[i] - prev[i] < MIN_PEAK_GROWTH]
        require(not idle, f"{name}: devices {idle} did no work — peak "
                f"bytes {prev} -> {cur}")
    require(lm["param_devices"] == n_dev,
            f"LM params live on {lm['param_devices']} devices, not {n_dev}")
    require(lm["largest_param_shard"] != lm["largest_param"],
            "LM params are replicated, not tensor-sharded")
    return {
        "flagship_mesh": mesh_lines[0],
        "lm_mesh": lm["mesh"],
        "peak_bytes_in_use": dict(zip(
            ("before", "after_flagship", "after_lm"), peaks)),
        "lm_bytes_in_use": [m["bytes_in_use"] for m in lm["memory"]],
    }


# ------------------------------------------------------------------ main

def main() -> int:
    try:
        import fedml_tpu  # noqa: F401
        from fedml_tpu.utils.compile_cache import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the fedml_tpu package is not importable from "
              f"here ({e}); run from the root of a checkout", file=sys.stderr)
        return 2
    from fedml_tpu.core.telemetry import get_tracer, install_jax_collectors

    cache_dir = configure_compile_cache()   # before the first compile
    install_jax_collectors()
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    t_start, totals = time.perf_counter(), {}

    def run(name, fn, *args, **kwargs):
        seen = compiles_since({}, time.time())
        wall0, t0 = time.time(), time.perf_counter()
        with get_tracer().span("chip_smoke." + name):
            out = fn(*args, **kwargs)
        out = {**out, "wall_s": round(time.perf_counter() - t0, 2),
               "compile": compiles_since(seen, wall0)}
        for key, value in out["compile"].items():
            totals[key] = round(totals.get(key, 0) + value, 2)
        print(f"[chip_smoke] {name}: {json.dumps(out)}", flush=True)
        return out

    dev = stage_device()
    if dev["platform"] != "tpu":
        # nothing goes to stdout off the chip: no result was produced
        print(f"chip_smoke: platform is {dev['platform']!r} "
              f"({dev['device_kind']}), not 'tpu' — this check only passes "
              "on the chip", file=sys.stderr)
        return 1
    print(f"[chip_smoke] device: {json.dumps(dev)}", flush=True)
    print(f"[chip_smoke] compile cache: "
          f"{cache_dir or 'JAX_COMPILATION_CACHE_DIR (placed from outside)'}",
          flush=True)

    lm4 = None
    if dev["device_count"] >= 4:
        # the anchor cohort of 10 does not divide a client axis of 4: the
        # engine pads it (packed lanes) and the facade builds the mesh over
        # all four — so this is the anchor config, not a cohort of 12
        before = memory_stats()
        fl4 = run("four_chips_flagship", stage_flagship, FLAGSHIP,
                  FLAGSHIP_ROUNDS, backend="TPU")
        lm4 = run("four_chips_lm", stage_lm, LM_MODEL, LM_SEQ, LM_BATCH,
                  LM_STEPS, dp=2, tp=2)
        run("four_chips", check_four_chips, before, fl4, lm4)

    fl = run("flagship", stage_flagship, FLAGSHIP, FLAGSHIP_ROUNDS)
    require(any("schedule=packed" in ln for ln in fl["engaged"]),
            f"flagship did not take the packed schedule: {fl['engaged']}")

    lm = run("lm", stage_lm, LM_MODEL, LM_SEQ, LM_BATCH, LM_STEPS)
    require(lm["attention_impl"] == "flash",
            f"attention dispatched to {lm['attention_impl']} at T={LM_SEQ}")
    # the forward kernel, remat's recompute of it and the one backward kernel:
    # the layers share one lowering of each
    require(lm["mosaic_calls_lowered"] >= 3,
            f"LM step lowers to {lm['mosaic_calls_lowered']} Mosaic calls: "
            "the flash kernels did not engage compiled")
    if lm4 is not None:
        gap = abs(lm4["loss"][0] - lm["loss"][0])
        require(gap <= 2e-2, "dp=2 x tp=2 first-step loss differs from the "
                f"one-chip step by {gap:.4f}")
    hybrid = run("hybrid_lm", stage_hybrid_lm, HYBRID_LM, HYBRID_SEQ,
                 HYBRID_BATCH, LM_STEPS)
    # flash forward and backward (2); the grouped product into the hidden
    # width and out of it, each one's transpose by rows and by weights (6);
    # the row moves: rows out, rows back (2)
    require(hybrid["attention_impl"] == "flash"
            and hybrid["mosaic_calls_lowered"] >= 2 + 6 + 2,
            f"the hybrid LM's kernels did not engage compiled: {hybrid}")
    mixers = run("hybrid_mixers", stage_hybrid_lm, HYBRID_MIXERS, HYBRID_SEQ,
                 HYBRID_BATCH, LM_STEPS)
    # flash forward and backward (2); the two grouped products of a relu2
    # expert, each one's transpose by rows and by weights (6)
    require(mixers["attention_impl"] == "flash"
            and mixers["mosaic_calls_lowered"] >= 2 + 6,
            f"the one-mixer decoder's kernels did not engage compiled: {mixers}")
    looped = run("looped_lm", stage_hybrid_lm, LOOPED_LM, HYBRID_SEQ,
                 HYBRID_BATCH, LM_STEPS)
    # flash forward and backward, at head width 128 with no K/V repeat
    require(looped["attention_impl"] == "flash"
            and looped["mosaic_calls_lowered"] >= 2,
            f"the looped decoder's kernels did not engage compiled: {looped}")
    scan = run("ssd_vs_sequential", check_ssd_vs_sequential, *SSD_SHAPE)
    require((scan["impl"], scan["kernel"]) == ("chunked", "pallas"),
            f"the scan took {scan['impl']} by {scan['kernel']}")
    for shape in ROW_MOVES:
        moves = run("row_moves", check_row_moves, *shape)
        require(moves["mosaic_calls_lowered"] == 2,  # rows out, rows back
                f"the row moves did not run as compiled Mosaic calls: {moves}")
    fvd = run("flash_vs_dense", check_flash_vs_dense, LM_SEQ,
              LM_MODEL["num_heads"], LM_MODEL["dim"] // LM_MODEL["num_heads"])
    require(fvd["mosaic_calls_lowered"] >= 2, "flash check ran interpreted")
    # the dk/dv + dq pair, which no model here is long enough to take
    split = run("flash_vs_dense_split", check_flash_vs_dense,
                FLASH_SPLIT_SEQ, 2, 64)
    require(split["mosaic_calls_lowered"] >= 3,
            "the split backward did not engage compiled")
    # the same pair over a band, at the window cell's length: the key
    # blocks outside it skipped, the lower edge's masked
    band = run("flash_vs_dense_window", check_flash_vs_dense, **FLASH_WINDOW)
    require(band["mosaic_calls_lowered"] >= 3,
            "the windowed pair did not engage compiled")

    run("fused_gram_refuses", check_gram_refuses, GRAM_REFUSED)
    kernels = [run("fused_gram", check_gram, GRAM_SHAPE),
               run("fused_sanitize_krum", check_sanitize_krum,
                   model_param_shapes(FLAGSHIP, (32, 32, 3), 10),
                   FLAGSHIP["client_num_per_round"]),
               run("fused_quantize_pack_q8", check_quant, QUANT_SHAPE, 8),
               run("fused_quantize_pack_q4", check_quant, QUANT_SHAPE, 4)]
    kernels += [run(f"conv2d_pallas_{hw}x{hw}x{c}", check_conv, (b, hw, c))
                for b, hw, c in CONV_STAGES]
    for res in kernels:
        require(res["mosaic_calls_lowered"] >= 1,
                f"kernel did not run as a compiled Mosaic call: {res}")

    print(f"[chip_smoke] total: wall {time.perf_counter() - t_start:.1f}s, "
          f"compile {json.dumps(totals)}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

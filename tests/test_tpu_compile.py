"""Programs compiled for a described TPU v5e, with no chip attached: what
XLA:TPU makes of a piece of the main path, which no CPU lowering shows.
Nothing runs. Keep every such test in this one file: the topology is
described inside a fixture, by the one worker that is handed the file."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.ops.losses import lm_cross_entropy


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as env:
        env.setenv("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001  (whatever keeps libtpu out)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


# the LFM2 cell's layout (one chip, 1/8 of the vocabulary) and the trainer's
# tp = 2, where each chip holds half the head's columns: 8192 of 16384, and
# chip_smoke.py's 16000 of 32000, between the widths that showed the rewrite
# (<= 8192) and those that did not (>= 16384)
@pytest.mark.parametrize("tp,V", [(1, 8192), (2, 16384), (2, 32000)],
                         ids=["one_chip-8192", "tp2-8192", "tp2-16000"])
def test_head_and_loss_compile_without_a_reduce_window(topo, tp, V):
    """On 3-D logits with V <= 8192 XLA:TPU turned ``log_softmax``'s
    ``x - max(x)`` into a ``reduce-window`` as wide as the vocabulary: 47 ms
    of the LFM2 cell's step (PERF.md section 6, PR 30). The shape is that
    cell's at a small B x T x D, which showed the rewrite."""
    B, T, D = 2, 512, 256
    mesh = Mesh(np.array(topo.devices[:tp]), ("model",))

    def head_and_loss(h, w, targets):
        def loss(h, w):
            logits = jnp.dot(h.reshape(B * T, D), w.T,
                             preferred_element_type=jnp.float32)
            return lm_cross_entropy(logits.reshape(B, T, V), targets)
        return jax.value_and_grad(loss, argnums=(0, 1))(h, w)

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    text = jax.jit(head_and_loss).lower(
        sds((B, T, D), jnp.bfloat16), sds((V, D), jnp.bfloat16, P("model")),
        sds((B, T), jnp.int32)).compile().as_text()
    assert "fusion(" in text and f"f32[{B},{T},{V // tp}]" in text
    assert not re.findall(r"reduce-window\(", text)
    # the row max, the two sums and the hidden states' gradient cross the
    # chips; the logits never do
    assert ("all-reduce" in text) == (tp > 1) and "all-gather" not in text


def test_the_expert_layer_compiles_with_no_slot_wide_array_and_no_row_gather(
        topo, monkeypatch):
    """The routed experts at the LFM2 cell's widths (D = 2048, 8 of 64
    experts held, top-4), forward and backward, at a small N. Before PR 33
    the combine gathered a row for every one of the N * k slots into an
    (N * k, D) array, its backward wrote that array's cotangent, and XLA:TPU
    ran such row gathers at a fifth of HBM's rate (PERF.md section 6). Now
    the rows move inside ``ops/pallas/row_move.py``'s kernels: what XLA
    still gathers is scalars (indices, weights), and every Mosaic call of
    the layer names its scope, the shuffle's in the backward too: the
    benchmark's ``moe_shuffle_ms`` and ``moe_ms`` find them by it."""
    from fedml_tpu.ops.moe import dropless_moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    N, D, F, E, held, k = 1024, 2048, 1536, 64, 8, 4
    one_chip = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())

    def loss(x, gate, bias, w1, w3, w2):
        out, stats = dropless_moe(x, gate, bias, w1, w3, w2, top_k=k,
                                  experts_held=(0, held))
        return out.astype(jnp.float32).sum(), stats

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    text = jax.jit(jax.value_and_grad(loss, (0, 1, 3, 4, 5), has_aux=True)).lower(
        sds((N, D), jnp.bfloat16), sds((D, E), jnp.float32),
        sds((E,), jnp.float32), sds((held, D, F), jnp.float32),
        sds((held, D, F), jnp.float32), sds((held, F, D), jnp.float32),
    ).compile().as_text()
    # (the larger of the two buffers has N * k + 256 rows, so N * k rows by
    # D, in the layer's layout or the kernel's slabs, is only a slot's array)
    assert f"[{N},{D}]" in text
    assert not re.findall(rf"\[{N * k},{D}\]|\[{N},{k},{D}\]|\[{N * k},\d+,128\]",
                          text)
    gathers = re.findall(r"= (\S+) gather\(", text)
    assert gathers and not [g for g in gathers if f"{D}]" in g or ",128]" in g]
    kernels = [re.search(r'op_name="([^"]*)"', line).group(1)
               for line in text.splitlines() if "tpu_custom_call" in line]
    shuffle = [name for name in kernels if re.search(
        r"jit\(_(rows_from_tokens|tokens_from_rows)\)", name)]
    # two buffer sizes x (the forward's two moves; the first of them again
    # under that size's checkpoint, where the second's result is not needed;
    # the backward's two); beside three grouped products each time and their
    # three transposes by weights
    assert len(shuffle) == 2 * 5 and len(kernels) == 2 * (5 + 12)
    assert all("moe.shuffle." in name for name in shuffle)
    assert sum("transpose(jvp" in name and "rematted" not in name
               for name in shuffle) == 2 * 2
    assert all("moe.experts" in name for name in kernels if name not in shuffle)


def test_the_relu2_expert_block_compiles_at_widths_that_are_no_tile_multiples(
        topo, monkeypatch):
    """The routed experts at the Nemotron cell's widths (D = 2688, F = 1856 =
    14.5 x 128, 8 of 128 experts held, top-6, no ``w3``), forward and
    backward, at a small N. megablox hands one tile to the product, to its
    transpose by rows (where k and n change places) and to its transpose by
    weights: a k or n tile that is neither a multiple of 128 nor that
    dimension whole in all three is refused by Mosaic only here, at the
    backward's compile (PERF.md section 6, PR 34). A bf16 row of 2688 is 21
    sublanes, 10.5 words, and fills no slab the row-move kernels read: since
    PR 37 it lies in one padded up to a height they do, so this is where
    Mosaic's verdict on the padded slab is had without a chip. As at the
    LFM2 cell's widths: the rows move inside the kernels at both buffer
    sizes, no array of N * k rows exists in the layer's layout or the
    slabs', what XLA still gathers is scalars, and every Mosaic call names
    its scope, the backward's row moves too."""
    from fedml_tpu.ops.moe import GMM_TILE_AT, _gmm_tiling, dropless_moe
    from fedml_tpu.ops.pallas.row_move import _slab_sublanes

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    N, D, F, E, held, k = 1024, 2688, 1856, 128, 8, 6
    assert {(D, F), (F, D)} <= set(GMM_TILE_AT)
    assert _slab_sublanes(D, jnp.bfloat16) > D // 128   # a padded slab
    for shape in ((D, F), (F, D)):
        tile = _gmm_tiling(4096, *shape)
        assert tile[0] == 256 and all(t % 128 == 0 for t in tile[1:]), tile
    one_chip = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())

    def loss(x, gate, bias, w1, w2):
        out, stats = dropless_moe(x, gate, bias, w1, None, w2, top_k=k,
                                  experts_held=(0, held), scale=2.5,
                                  form="relu2")
        return out.astype(jnp.float32).sum(), stats

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    text = jax.jit(jax.value_and_grad(loss, (0, 1, 3, 4), has_aux=True)).lower(
        sds((N, D), jnp.bfloat16), sds((D, E), jnp.float32),
        sds((E,), jnp.float32), sds((held, D, F), jnp.float32),
        sds((held, F, D), jnp.float32)).compile().as_text()
    assert f"[{N},{D}]" in text
    assert not re.findall(rf"\[{N * k},{D}\]|\[{N},{k},{D}\]|\[{N * k},\d+,128\]",
                          text)
    gathers = re.findall(r"= (\S+) gather\(", text)
    assert gathers and not [g for g in gathers if f"{D}]" in g or ",128]" in g]
    kernels = [re.search(r'op_name="([^"]*)"', line).group(1)
               for line in text.splitlines() if "tpu_custom_call" in line]
    shuffle = [name for name in kernels if re.search(
        r"jit\(_(rows_from_tokens|tokens_from_rows)\)", name)]
    # two buffer sizes x (the five row moves of the LFM2 test above; two
    # products, both again under that size's checkpoint, and each one's two
    # transposes)
    assert len(shuffle) == 2 * 5 and len(kernels) == 2 * (5 + 8)
    assert all("moe.shuffle." in name for name in shuffle)
    assert sum("transpose(jvp" in name and "rematted" not in name
               for name in shuffle) == 2 * 2
    assert all("moe.experts" in name for name in kernels if name not in shuffle)


@pytest.mark.parametrize("batch,T", [(2, 1024), (1, 8192)],
                         ids=["b2_t1024", "the_cell_b1_t8192"])
def test_the_mamba2_core_compiles_with_its_scopes_forward_and_backward(
        topo, monkeypatch, batch, T):
    """``ops/ssd.py mamba2_core`` at the Nemotron cell's widths (64 heads x
    64, state 128, 8 groups, chunk 128), forward and backward, at two batch
    rows of 8 chunks and at the cell's one row of 64: the scan is the kernel
    pair of ``ops/pallas/ssd.py`` (one Mosaic call each way, both inside
    scoped VMEM), no array of chunks x heads x L x L elements is left (the
    decays and masked scores XLA's chunked form kept in HBM), and the three
    scopes name the ops in both passes, the kernels' calls too, which is what
    ``ssd_ms`` and ``ssd_roofline`` read."""
    from fedml_tpu.ops.ssd import mamba2_core

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    heads, head_dim, state, groups, chunk = 64, 64, 128, 8, 128
    inner, conv_dim = heads * head_dim, heads * head_dim + 2 * groups * state
    one_chip = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
    sds = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)

    def loss(*args):
        return mamba2_core(*args, heads=heads, head_dim=head_dim, state=state,
                           groups=groups, chunk=chunk, eps=1e-5
                           ).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, tuple(range(7)))).lower(
        sds(batch, T, inner + conv_dim + heads, dtype=jnp.bfloat16),
        sds(conv_dim, 4), sds(conv_dim), sds(heads), sds(heads), sds(heads),
        sds(inner)).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line
             and "custom-call(" in line]
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in calls]
    assert len(names) == 2 and all("ssd.core" in n for n in names), names
    assert sorted("transpose(" in n for n in names) == [False, True], names
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("ssd.conv", "ssd.core", "ssd.gate_norm"):
        assert [n for n in names if scope in n and "transpose(" in n], scope
        assert [n for n in names if scope in n and "transpose(" not in n], scope
    # 8,388,608 elements a row at T = 1024, 67,108,864 at the cell's; nor
    # a group's (L, L) scores, the other array the dual form kept in HBM
    shapes = [tuple(int(d) for d in dims.split(",")) for dims in re.findall(
        r"\b(?:f32|bf16|pred|s32)\[([0-9,]+)\]", text)]
    scores = batch * (T // chunk) * heads * chunk * chunk
    assert scores not in {int(np.prod(shape)) for shape in shapes}
    assert not [shape for shape in shapes if shape[-2:] == (chunk, chunk)]

def test_the_looped_step_compiles_at_the_cells_size_inside_the_chip(
        topo, monkeypatch):
    """The whole training step of the Ouro cell (``benchmark/configs/
    ouro_2_6b.json`` at B = 1, T = 4096: eight layers applied four times,
    four head products of 49152 columns under the objective's checkpoint,
    AdamW over 612 M parameters) compiled for one described v5e: it fits the
    chip's 16 GB, the flash kernels are in it, and the scopes ``ut.pass``,
    ``ut.head`` and ``ut.exit`` name ops of the forward and of the backward
    pass, which is what ``ut_stack_ms``, ``ut_head_ms`` and ``ut_exit_ms``
    read. Nothing is placed or run: the parameters are shapes."""
    import json
    import os

    import optax

    from fedml_tpu.models.hybrid_lm import HybridLM
    from fedml_tpu.parallel.mesh import AXIS_DATA, AXIS_MODEL, AXIS_SEQ
    from fedml_tpu.parallel.trainer import DistributedLMTrainer, DistTrainConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    monkeypatch.syspath_prepend(bench)
    from runners import ouro_step  # the cell's own mapping onto DecoderConfig

    with open(os.path.join(bench, "configs", "ouro_2_6b.json")) as f:
        cfg = json.load(f)
    B, T = 1, 4096
    model = ouro_step.decoder_config(cfg)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1, 1),
                (AXIS_DATA, AXIS_SEQ, AXIS_MODEL))
    rep = NamedSharding(mesh, P())
    t = DistributedLMTrainer.__new__(DistributedLMTrainer)
    t.cfg = DistTrainConfig(exit_entropy_weight=cfg["exit_entropy_beta"])
    t.mesh = mesh
    t.model = HybridLM(model, dtype=jnp.bfloat16, mesh=mesh, remat=True)
    t.step_stats = t.model.STEP_STATS
    t.opt = optax.adamw(t.cfg.lr, weight_decay=t.cfg.weight_decay)
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), tree)
    params = on_chip(jax.eval_shape(
        t.model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert sum(a.size for a in jax.tree.leaves(params)) == 612_438_017
    t.param_shardings = jax.tree.map(lambda a: a.sharding, params)
    t.constants = {}
    t.batch_sharding = NamedSharding(mesh, P(AXIS_DATA, AXIS_SEQ))
    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=t.batch_sharding)
    compiled = t._build_train_step().lower(
        params, on_chip(jax.eval_shape(t.opt.init, params)), {}, tokens, tokens
    ).compile()
    memory = compiled.memory_analysis()
    peak = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    # 9.80 GB of parameters, gradients' sums and moments; under the chip's
    # 16 GB with room for the allocator (PERF.md section 6 has the reading)
    assert 9.8e9 < peak < 15.0e9, peak
    text = compiled.as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    kernels = [re.search(r'op_name="([^"]*)"', line).group(1)
               for line in text.splitlines()
               if "tpu_custom_call" in line and "custom-call(" in line]
    assert kernels and all("_local_attention" in n and "ut.pass" in n
                           for n in kernels), kernels[:3]
    for scope in ("ut.pass", "ut.head", "ut.exit"):
        assert [n for n in names if scope in n and "transpose(" in n], scope
        assert [n for n in names if scope in n and "transpose(" not in n], scope
    # each scope's name finds its own ops only: the passes hold no op of the
    # objective, and neither of its two scopes holds the other's
    assert not [n for n in names if "ut.pass" in n
                and ("ut.head" in n or "ut.exit" in n or "lm.loss" in n)]
    assert not [n for n in names if "ut.head" in n and "ut.exit" in n]


@pytest.mark.parametrize("H,Dh", [(4, 64), (2, 128)], ids=["dh64", "dh128"])
def test_the_flash_pair_compiles_with_striped_diagonal_blocks(topo, monkeypatch,
                                                             H, Dh):
    """The GPT-2 cell's attention (T = 1024: one diagonal forward block of
    eight stripes, backward blocks of four) at two head groups, and a head
    width of 128, forward and backward compiled for the v5e: Mosaic takes
    the striped bodies inside scoped VMEM, and they stay the forward kernel
    and the fused backward kernel, two call sites (PERF.md section 6, PR 39:
    the whole GPT-2 step kept its three)."""
    from fedml_tpu.core.telemetry import get_registry
    from fedml_tpu.ops.pallas import flash_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
    sds = jax.ShapeDtypeStruct((2, 1024, H, Dh), jnp.bfloat16,
                               sharding=one_chip)
    striped = lambda: get_registry().counter(  # noqa: E731
        "fedml_flash_diagonal_total", impl="striped", seq_len=1024,
        **{"pass": "bwd"}).value
    before = striped()
    text = jax.jit(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, True).astype(jnp.float32).sum(), (0, 1, 2))).lower(
        sds, sds, sds).compile().as_text()
    assert striped() == before + 1
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line]
    assert len(calls) == 2, calls
    assert sorted("_flash_backward" in n for n in calls) == [False, True]


@pytest.mark.parametrize("B,H", [(1, 28), (2, 2)], ids=["cell", "two_groups"])
def test_the_windowed_flash_pair_compiles_with_its_scope(topo, monkeypatch, B, H):
    """The SmallThinker cell's windowed attention (T = 16384, head width
    128, bf16, a band of 4096; and at two head groups and B = 2, where a
    probe at one group once compiled what two refused) forward and backward
    compiled for the v5e: Mosaic takes the band's bodies inside scoped VMEM;
    at this length the backward is the split pair (the fused one's
    whole-sequence dq does not fit), so three call sites, each under the
    ``attn.window`` scope the launchers open themselves, which is what
    ``attn_window_ms.st21`` and the two ``flash_window_*_roofline.st21``
    read."""
    from fedml_tpu.ops.pallas import flash_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
    sds = jax.ShapeDtypeStruct((B, 16384, H, 128), jnp.bfloat16,
                               sharding=one_chip)
    text = jax.jit(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, True, window=4096).astype(jnp.float32).sum(),
        (0, 1, 2))).lower(sds, sds, sds).compile().as_text()
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line]
    assert len(calls) == 3, calls
    assert all("attn.window" in n for n in calls), calls
    assert sorted("_flash_backward" in n for n in calls) == [False, True, True]
    assert [n for n in calls if "transpose(" in n]  # the backward's, named

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

"""The reader of the program's counters (``readers/program_counter.py``):
over the program's registry with and without the attention dispatch counter,
and ``attn_flash_share.lm``'s data file at the LM cell's traffic."""

from __future__ import annotations

import importlib.util
import os

import pytest

import test_harness as th
from test_harness import bench, manifest  # noqa: F401  (fixtures)
from fedml_tpu.core import telemetry

ARGS = {"counter": "fedml_attention_dispatch_total", "label": "impl",
        "value": "flash"}
CTX = {"traffic": {"seq_len": 1024}}


@pytest.fixture(scope="module")
def reader():
    spec = importlib.util.spec_from_file_location(
        "bench_program_counter",
        os.path.join(th.BENCH, "readers", "program_counter.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def registry(monkeypatch):
    reg = telemetry.MetricsRegistry(enabled=True)
    monkeypatch.setattr(telemetry, "get_registry", lambda: reg)
    return reg


@pytest.mark.parametrize("flash,dense,want", [
    (48, 0, 100.0), (36, 12, 75.0), (0, 24, 0.0), (0, 0, None)])
def test_share_of_one_label_over_the_family(reader, registry, flash, dense,
                                            want):
    # another family, and a counter whose name only starts alike, stay out
    registry.counter("fedml_lm_steps_total").inc(140)
    registry.counter("fedml_attention_dispatch_total_other", impl="flash").inc(9)
    for impl, n in (("flash", flash), ("dense", dense)):
        if n:
            registry.counter(ARGS["counter"], impl=impl).inc(n)
    got = reader.read({}, **ARGS)
    assert got == want if want is None else got == pytest.approx(want)


def test_a_tenant_label_beside_it_does_not_hide_the_series(reader, registry):
    registry.counter(ARGS["counter"], impl="flash", tenant="a").inc(3)
    registry.counter(ARGS["counter"], impl="dense", tenant="a").inc(1)
    assert reader.read({}, **ARGS) == pytest.approx(75.0)


def test_at_traffic_leaves_other_lengths_out(reader, registry):
    """The LM step as the program counts it: 48 call sites at the cell's
    length, and the 24 of ``model.init`` traced over 8 tokens."""
    registry.counter(ARGS["counter"], impl="flash", seq_len=1024).inc(48)
    registry.counter(ARGS["counter"], impl="dense", seq_len=8).inc(24)
    assert reader.read(CTX, **ARGS) == pytest.approx(100 * 48 / 72)
    assert reader.read(CTX, at_traffic=["seq_len"], **ARGS) == 100.0
    # the failure the metric is there for: the rule refuses the cell's shape
    registry.counter(ARGS["counter"], impl="dense", seq_len=1024).inc(48)
    assert reader.read(CTX, at_traffic=["seq_len"], **ARGS) == 50.0
    assert reader.read({"traffic": {"seq_len": 2048}}, at_traffic=["seq_len"],
                       **ARGS) is None


def test_the_program_counts_and_the_reader_reads_it(reader, registry,
                                                    monkeypatch):
    """Through the program itself: every call site the rule resolved, under
    its length; none that named its ``impl``."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops import attention
    from fedml_tpu.ops.attention import multihead_attention

    # the module took the accessor by name when it was imported
    monkeypatch.setattr(attention, "get_registry", lambda: registry)
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)
    jax.jit(lambda q: multihead_attention(q, q, q, True, impl="flash"))(q)
    multihead_attention(q, q, q, True)  # not under a tracer: nothing to count
    assert reader.read({}, **ARGS) is None
    jax.jit(lambda q: multihead_attention(q, q, q, True))(q)
    assert reader.read({}, **ARGS) == 0.0  # off the chip T = 128 is dense
    monkeypatch.setattr(attention, "auto_attention_impl",
                        lambda *shape: "flash")
    jax.jit(lambda q: multihead_attention(q[:, :256], q[:, :256], q[:, :256]))(
        jnp.zeros((1, 256, 2, 64), jnp.float32))
    assert reader.read({}, **ARGS) == pytest.approx(50.0)
    assert registry.snapshot()["counters"] == {
        "fedml_attention_dispatch_total{impl=dense,seq_len=128}": 1.0,
        "fedml_attention_dispatch_total{impl=flash,seq_len=256}": 1.0}


def test_the_metrics_data_file_reads_the_lm_cell(bench, reader, registry):
    """``attn_flash_share.lm.json`` as the harness would call it: the share
    at the traffic's ``seq_len``. (The manifest does not enter the metric
    yet: PERF.md section 7.)"""
    spec = bench.load_json(os.path.join(
        th.BENCH, "layer_metrics", "attn_flash_share.lm.json"))
    assert spec == {"reader": "program_counter",
                    "args": dict(ARGS, at_traffic=["seq_len"])}
    traffic = bench.load_json(os.path.join(
        th.BENCH, "traffic", "t1024_b8_pretrain.json"))
    ctx = {"traffic": traffic}
    assert reader.read(ctx, **spec["args"]) is None  # as on the parent
    registry.counter(ARGS["counter"], impl="dense", seq_len=8).inc(24)
    assert reader.read(ctx, **spec["args"]) is None
    registry.counter(ARGS["counter"], impl="flash",
                     seq_len=traffic["seq_len"]).inc(48)
    assert reader.read(ctx, **spec["args"]) == 100.0

"""The whole step's share of the chip's peak: FLOPs the step needs (a
function of flops.py, named in the metric's file) x units of work per second
over the window, over chips x the peak of the printed device_kind, in percent.
The traced run's window leaves out the host time its own profiler switching
took."""

import importlib


def read(ctx: dict, flops_fn: str):
    flops = importlib.import_module("flops")
    per_unit = getattr(flops, flops_fn)(ctx["config"], ctx["traffic"])
    window = ctx["window"]
    rate = window["flop_units"] / (window["wall_s"] - window["trace_overhead_s"])
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["chips"]
    return per_unit * rate / peak * 100.0

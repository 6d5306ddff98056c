"""Idle share of the device over the traced slice, in percent, from
trace_reduce: 100 x (1 - union of device-op intervals / slice)."""


def read(ctx: dict):
    trace = ctx.get("trace")
    return None if not trace else 100.0 * trace["idle_share"]

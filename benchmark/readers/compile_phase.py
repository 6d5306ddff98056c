"""One phase of set-up's first step, from the program's own spans in the
ring that ``fedml_tpu.core.telemetry`` keeps (the program's public
observability API; nothing of its logic is copied here).

The program turns jax's compile phases into spans ``jax.trace``,
``jax.lower`` and ``jax.compile``, children of the span open when they ran,
each with the ``fun`` they were made for (``train_step``). The first
``lm.dispatch`` in the ring with such a child is set-up's first step,
whatever ran before it. Of its child ``phase`` for ``fun`` this returns the
seconds, or the attribute ``value`` names (``traces``: the trace phases
inside the outermost trace). Nothing where there is no such dispatch or
child, as on a program that records no compile phases."""


def read(ctx: dict, phase: str, fun: str = "train_step",
         value: str = "duration"):
    from fedml_tpu.core.telemetry import get_tracer

    spans = get_tracer().finished_spans()
    children: dict = {}  # a span's children arrive before it does
    for rec in spans:
        if rec["name"].startswith("jax."):
            children.setdefault(rec["parent_span_id"], []).append(rec)
    first = next((rec for rec in spans if rec["name"] == "lm.dispatch"
                  and rec["span_id"] in children), None)
    if first is None:
        return None
    found = [rec for rec in children[first["span_id"]]
             if rec["name"] == phase and rec.get("fun") == fun]
    if not found:
        return None
    rec = max(found, key=lambda r: r["duration"])
    return float(rec.get(value)) if rec.get(value) is not None else None

"""Host time of the program's own spans over the window, from the ring of
finished spans that ``fedml_tpu.core.telemetry`` keeps in memory (the
program's public observability API; nothing of its logic is copied here).

The named spans are summed per parent span (one ``lm.step`` has one
``lm.input_put`` and one ``lm.dispatch``), and the last ``attempted`` such
sums are the window's: the steps of set-up, which compile, come before them.
``once`` takes the one last sum instead (a span of set-up). Returns their
mean or maximum, in milliseconds or seconds; nothing where the ring holds
fewer sums than the window has steps, as on a program without these spans."""


def read(ctx: dict, spans: list, stat: str, unit: str = "ms",
         once: bool = False):
    from fedml_tpu.core.telemetry import get_tracer

    sums: dict = {}
    for rec in get_tracer().finished_spans():
        if rec["name"] in spans:
            key = rec["parent_span_id"] or rec["span_id"]
            sums[key] = sums.get(key, 0.0) + rec["duration"]
    wanted = 1 if once else ctx["window"]["attempted"]
    if wanted < 1 or len(sums) < wanted:
        return None
    values = list(sums.values())[-wanted:]  # dicts keep the order of arrival
    value = max(values) if stat == "max" else sum(values) / len(values)
    return value * {"ms": 1e3, "s": 1.0}[unit]

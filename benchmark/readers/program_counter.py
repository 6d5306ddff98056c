"""Share of one label value in a counter family of the program's own
registry (``fedml_tpu.core.telemetry.get_registry``, the program's public
observability API), over the whole process: the series of ``counter`` whose
``label`` is ``value``, over all of the family's series, in percent.
``at_traffic`` names labels that have to equal the cell's traffic parameter
of the same name: at the LM cell's ``seq_len`` a model's few-token ``init``
trace stays out of the share. Nothing where the family has counted nothing
there, as on a program without the counter."""


def read(ctx: dict, counter: str, label: str, value: str, at_traffic=()):
    from fedml_tpu.core.telemetry import get_registry

    here = {name: str(ctx["traffic"][name]) for name in at_traffic}
    wanted, total = 0.0, 0.0
    for key, count in get_registry().snapshot()["counters"].items():
        name, _, inner = key.partition("{")
        labels = dict(pair.split("=", 1)
                      for pair in inner.rstrip("}").split(",") if pair)
        if name != counter or any(labels.get(k) != v for k, v in here.items()):
            continue
        total += count
        if labels.get(label) == value:
            wanted += count
    return 100.0 * wanted / total if total else None

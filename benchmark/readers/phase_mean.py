"""Mean over the window's steps of one host phase the program reports
(seconds in, milliseconds out). Nothing to read where the runner has no
such phase."""


def read(ctx: dict, phase: str):
    values = ctx["window"].get("phases", {}).get(phase)
    if not values:
        return None
    return 1e3 * sum(values) / len(values)

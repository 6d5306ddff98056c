"""Programs compiled (not loaded from the cache) between the first and the
last timed step, from the CompileMeter around the window."""


def read(ctx: dict):
    return ctx["window"]["compiles"]

"""Host CPU time of the process (user + system, ``getrusage(RUSAGE_SELF)``)
over the window, per image answered in it. Argument: ``scale``."""


def read(ctx, scale=1000.0):
    if not ctx["images"]:
        return None
    return scale * (ctx["cpu_after"] - ctx["cpu_before"]) / ctx["images"]

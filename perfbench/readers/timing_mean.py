"""Mean, over the images answered in the window, of one key of the
``timings`` dict that ``transform_bytes`` fills for each image.

Arguments: ``key``, ``scale`` (default 1000: seconds to ms)."""


def read(ctx, key, scale=1000.0):
    values = [t[key] for t in ctx["timings"] if key in t]
    if not values:
        return None
    return scale * sum(values) / len(values)

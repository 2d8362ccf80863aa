"""Share of the traced window in which a chip runs no operation (1 minus
the union of its operations' intervals over the window), averaged over
the chips."""


def read(ctx):
    return {"value": 100.0 * ctx["trace"]["idle_share"], "unit": "%"}

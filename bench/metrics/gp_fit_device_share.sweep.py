"""Share of the traced window in which the device ran the GP bank's batched
L-BFGS fit (the ``_fit_packed`` programs)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    secs = sum(v for k, v in tr["programs"].items() if "_fit_packed" in k)
    if secs <= 0:
        return None
    return 100.0 * secs / tr["window_s"]

"""Share of the window in which Demeter's controllers assembled RGP
ensembles, with the lazy fits they set off (the program's
``demeter.ensemble`` spans; a span inside another counts once)."""


def read(ctx):
    ivs = sorted((s.ts_ns, s.ts_ns + s.dur_ns) for s in ctx.get("spans", ())
                 if s.name == "demeter.ensemble")
    total = end = 0
    for a, b in ivs:                  # the union: outermost spans only
        total += max(0, b - max(a, end))
        end = max(end, b)
    if total <= 0 or ctx["window_s"] <= 0:
        return None
    return 100.0 * total * 1e-9 / ctx["window_s"]

"""Share of the window in the sweep's shared GP model refresh, every stale
GP of the due controllers fitted in one bank dispatch and read back (the
program's ``sweep.model_refresh`` spans)."""


def read(ctx):
    secs = sum(s.dur_ns for s in ctx.get("spans", ())
               if s.name == "sweep.model_refresh") * 1e-9
    if secs <= 0 or ctx["window_s"] <= 0:
        return None
    return 100.0 * secs / ctx["window_s"]

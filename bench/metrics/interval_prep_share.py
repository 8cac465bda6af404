"""Share of the window in which the fused engine's interval step built its
K-tick planes on the host: clocks, RNG draws and failure staging (the
program's ``engine.fused.prepare`` spans)."""


def read(ctx):
    secs = sum(s.dur_ns for s in ctx.get("spans", ())
               if s.name == "engine.fused.prepare") * 1e-9
    if secs <= 0 or ctx["window_s"] <= 0:
        return None
    return 100.0 * secs / ctx["window_s"]

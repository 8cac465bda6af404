"""Share of the window in which the fused engine's interval step waited for
the scan and copied its carry and metric planes back into the host's
history (the program's ``engine.fused.readback`` spans)."""


def read(ctx):
    secs = sum(s.dur_ns for s in ctx.get("spans", ())
               if s.name == "engine.fused.readback") * 1e-9
    if secs <= 0 or ctx["window_s"] <= 0:
        return None
    return 100.0 * secs / ctx["window_s"]

"""Share of the window inside the sweep event loop's controller decisions
(the program's ``sweep.policy_block`` spans)."""


def read(ctx):
    secs = sum(s.dur_ns for s in ctx.get("spans", ())
               if s.name == "sweep.policy_block") * 1e-9
    if secs <= 0 or ctx["window_s"] <= 0:
        return None
    return 100.0 * secs / ctx["window_s"]

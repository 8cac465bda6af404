"""Share of the window in the sweep event loop's own host work: the time of
the program's ``sweep.run`` span not covered by its direct child spans (the
interval step's three spans, the model refresh and the policy blocks), which
leaves injection scheduling, forecast ingest and the Table 3 bookkeeping."""


def read(ctx):
    spans = ctx.get("spans", ())
    runs = [s for s in spans if s.name == "sweep.run"]
    if not runs or ctx["window_s"] <= 0:
        return None
    self_ns = 0
    for run in runs:
        end = run.ts_ns + run.dur_ns
        self_ns += run.dur_ns - sum(
            s.dur_ns for s in spans
            if s.depth == run.depth + 1 and s.ts_ns >= run.ts_ns
            and s.ts_ns + s.dur_ns <= end)
    return 100.0 * self_ns * 1e-9 / ctx["window_s"]

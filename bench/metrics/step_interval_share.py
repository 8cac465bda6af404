"""Share of the window spent inside ``FusedSweepExecutor.step_interval``
(host preparation of the K-tick planes, the scan dispatch and the copy of
its results back, which waits for the device), from the benchmark's own
wrapper."""


def read(ctx):
    if not ctx.get("step_interval_s") or ctx["window_s"] <= 0:
        return None
    return 100.0 * ctx["step_interval_s"] / ctx["window_s"]

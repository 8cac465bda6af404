"""Device milliseconds of the fused engine's interval scan
(``fused_interval_scan``) per 1000 scenario-steps of the traced window."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    secs = sum(v for k, v in tr["programs"].items()
               if "fused_interval_scan" in k)
    if secs <= 0 or not ctx["scenario_steps"]:
        return None
    return 1000.0 * secs / (ctx["scenario_steps"] / 1000.0)

"""Reads of one GP outside the GP bank's batched posterior (the program's
``gp.single_reads`` counter: ``GP.posterior``, through it ``GP.sample``, and
``GP.loo_samples``) per 1000 scenario-steps of the traced window. The
program's metrics are on only in that window, so the registry holds it
alone."""


def read(ctx):
    from repro import obs
    n = obs.snapshot()["counters"].get("gp.single_reads")
    if n is None or not ctx["scenario_steps"]:
        return None
    return n / (ctx["scenario_steps"] / 1000.0)

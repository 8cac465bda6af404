"""Packed GP-read dispatches of the RGPE path (the program's
``gp.packed_reads`` counter: one per build's reads, one per ensemble
posterior read) per 1000 scenario-steps of the traced window. The
program's metrics are on only in that window, so the registry holds it
alone."""


def read(ctx):
    from repro import obs
    n = obs.snapshot()["counters"].get("gp.packed_reads")
    if n is None or not ctx["scenario_steps"]:
        return None
    return n / (ctx["scenario_steps"] / 1000.0)

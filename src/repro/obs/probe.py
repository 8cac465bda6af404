"""Contract probes proving instrumentation adds zero ops to compiled HLO.

The whole obs design rests on one invariant: spans and metrics live
strictly on the *host* side of the jit boundary, so the compiled
programs are byte-for-byte the same whether obs is enabled or not.
:func:`instrumentation_probe` turns that claim into a checkable
``ContractProbe``:

1. trace the target function once with obs forced **off** and record its
   jaxpr primitive count — the uninstrumented baseline;
2. hand ``scripts/check_contracts.py`` a wrapper that re-traces the same
   function with obs forced **on**, under a ``CompilationContract`` whose
   ``max_primitives`` is pinned to that baseline and which forbids host
   callbacks.

If instrumentation ever leaks into the traced computation (a
``debug_print``, a callback, an extra reduction for a metric), the
primitive count grows past the pinned baseline or a callback primitive
appears, and the analysis CI job goes red.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from . import trace as _trace

__all__ = ["instrumentation_probe"]


def instrumentation_probe(name: str, fn: Callable, args: Tuple,
                          kwargs: Optional[Dict[str, Any]] = None,
                          static_argnums: Sequence[int] = (),
                          x64: bool = False,
                          note: str = "") -> Any:
    """Build a ContractProbe pinning ``fn``'s primitive count with obs
    enabled to its obs-disabled baseline (zero added ops, no callbacks)."""
    import jax

    from ..analysis.contracts import (CompilationContract, ContractProbe,
                                      jaxpr_summary)

    kwargs = dict(kwargs or {})

    def _baseline_primitives() -> int:
        # Mirror check_contract's counting exactly (jit wrapper included,
        # which contributes one outer pjit primitive) so the pinned budget
        # is apples-to-apples with what the probe later measures.
        jitted = jax.jit(fn, static_argnums=tuple(static_argnums))
        with _trace.force_disabled():
            closed = jax.make_jaxpr(
                lambda *a: jitted(*a, **kwargs),
                static_argnums=tuple(static_argnums))(*args)
        prims, _ = jaxpr_summary(closed)
        return len(prims)

    if x64:
        with jax.enable_x64():
            baseline = _baseline_primitives()
    else:
        baseline = _baseline_primitives()

    def _with_obs(*a: Any, **kw: Any) -> Any:
        # Forcing the enabled flag at trace time exercises every obs call
        # site on the traced path; the contract then proves none of them
        # contributed an op.
        with _trace.force_enabled():
            return fn(*a, **kw)

    # Pre-jit with the statics declared: check_contract wraps bare
    # callables in a plain jax.jit, which cannot carry non-array statics
    # like ClusterModel.
    traced_with_obs = jax.jit(_with_obs,
                              static_argnums=tuple(static_argnums))

    contract = CompilationContract(
        name=name,
        max_primitives=baseline,
        forbid_callbacks=True,
        note=note or (f"obs instrumentation must add zero ops: primitive "
                      f"count pinned to the obs-disabled baseline "
                      f"({baseline}) and host callbacks forbidden"),
    )
    return ContractProbe(contract=contract, fn=traced_with_obs, args=args,
                         kwargs=kwargs, x64=x64,
                         static_argnums=tuple(static_argnums))

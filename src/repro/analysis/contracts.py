"""Compilation contracts: machine-checked invariants of jitted hot paths.

The sweep engine's performance story rests on properties the type system
cannot see: the sharded step must compile to *zero* cross-scenario
collectives, the persistent buffers must actually be donated (an
``input_output_alias`` entry in the compiled module, not just a
``donate_argnums`` at the call site), nothing may upcast to float64 in a
float32 path, no host callback may hide inside a ``lax.scan`` body, and the
jit cache must not retrace per tick. Any one of these regressing silently
erases the batching/sharding wins while every numerical test stays green.

This module pins them statically:

* :class:`CompilationContract` — a declarative bundle of invariants;
* :func:`check_contract` — lowers + compiles a function once and walks both
  the jaxpr (primitives, dtypes, callbacks-in-loops) and the compiled HLO
  text (forbidden/required ops, donation) against a contract;
* :class:`ContractProbe` — how a registry entry packages its hot-path entry
  point with example arguments and its contract (see
  :meth:`repro.core.registry.Registry.attach_contract`);
* :func:`count_traces` — a caching-aware trace counter for recompile
  budgets (bucketing bugs show up as a cache that grows per call).

Deliberately dependency-free inside the repo (stdlib + jax only) so every
layer — kernels, banks, engines — can declare contracts without cycles.
"""
from __future__ import annotations

from collections import Counter
from dataclasses import dataclass, field, replace
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple,
                    Union)

__all__ = [
    "COLLECTIVE_HLO_OPS", "CALLBACK_PRIMITIVES", "LOOP_PRIMITIVES",
    "CompilationContract", "ContractViolation", "ContractReport",
    "ContractProbe", "check_contract", "run_probe", "jaxpr_summary",
    "count_traces", "host_probe",
]

#: HLO ops that imply cross-device communication. A scenario-sharded hot
#: path must compile to none of these (every per-step operation is
#: elementwise over the scenario axis).
COLLECTIVE_HLO_OPS: Tuple[str, ...] = (
    "all-reduce", "all-gather", "all-to-all", "collective-permute",
    "reduce-scatter", "collective-broadcast",
)

#: JAX primitives that call back into the host. Inside a jitted hot path —
#: and fatally, inside a ``scan``/``while`` body — they serialize the device
#: stream on the Python interpreter.
CALLBACK_PRIMITIVES: Tuple[str, ...] = (
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
)

#: Structured-control-flow primitives whose bodies we descend into with
#: ``in_loop=True`` (a callback *here* fires once per carried step).
LOOP_PRIMITIVES: Tuple[str, ...] = ("scan", "while", "fori_loop")


@dataclass(frozen=True)
class ContractViolation:
    """One broken invariant: which contract field, and what was seen."""

    field: str
    message: str

    def __str__(self) -> str:
        return f"[{self.field}] {self.message}"


@dataclass(frozen=True)
class CompilationContract:
    """Declarative invariants for one compiled hot-path entry point.

    Every field is optional; an empty contract passes trivially. Checked
    fields:

    ``forbidden_hlo``
        Op substrings that must *not* appear in ``compile().as_text()``
        (e.g. :data:`COLLECTIVE_HLO_OPS` for sharded steps, ``("fusion",)``
        never — see docs/ANALYSIS.md for the catalog).
    ``required_hlo``
        Op substrings that *must* appear (e.g. ``("while",)`` when a path
        is expected to stay a fused loop rather than unroll).
    ``donation``
        ``True`` requires at least one ``input_output_alias`` entry in the
        compiled module — i.e. the call site's ``donate_argnums`` was
        actually honored by XLA, not dropped by a copy.
    ``max_primitives``
        Ceiling on the recursive jaxpr equation count (catches accidental
        unrolling / vmap-of-scan blowups before they hit compile times).
    ``dtype_ceiling``
        ``"float32"`` forbids any float64/complex128 intermediate anywhere
        in the jaxpr; ``"float64"`` (or None) allows them. The f64 paths in
        this repo are *deliberate* (they mirror NumPy oracles bit-for-bit)
        and say so in their contracts.
    ``forbid_callbacks``
        No :data:`CALLBACK_PRIMITIVES` anywhere in the jaxpr; violations
        inside ``scan``/``while`` bodies are reported as such.
    ``max_traces``
        Recompile budget for :func:`count_traces` probes (a probe that
        exercises the real bucketing workload reports its trace count
        through :attr:`ContractProbe.traces`).
    """

    name: str = ""
    forbidden_hlo: Tuple[str, ...] = ()
    required_hlo: Tuple[str, ...] = ()
    donation: Optional[bool] = None
    max_primitives: Optional[int] = None
    dtype_ceiling: Optional[str] = None
    forbid_callbacks: bool = True
    max_traces: Optional[int] = None
    #: free-text rationale surfaced in reports (why these invariants)
    note: str = ""

    def named(self, name: str) -> "CompilationContract":
        """A copy of this contract carrying ``name`` (for registry reuse)."""
        return replace(self, name=name)


@dataclass
class ContractReport:
    """Outcome of checking one entry point against one contract."""

    name: str
    ok: bool
    violations: List[ContractViolation] = field(default_factory=list)
    n_primitives: int = 0
    dtypes: Tuple[str, ...] = ()
    n_traces: Optional[int] = None
    note: str = ""

    def summary(self) -> str:
        head = f"{self.name}: " if self.name else ""
        if self.ok:
            extra = f", traces={self.n_traces}" if self.n_traces is not None \
                else ""
            return (f"{head}OK ({self.n_primitives} primitives, "
                    f"dtypes={{{', '.join(self.dtypes)}}}{extra})")
        if not self.violations:       # a probe that failed before checking
            return f"{head}FAILED — {self.note or 'no report'}"
        lines = "\n  ".join(str(v) for v in self.violations)
        return f"{head}{len(self.violations)} violation(s)\n  {lines}"

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "ok": self.ok,
                "violations": [{"field": v.field, "message": v.message}
                               for v in self.violations],
                "n_primitives": self.n_primitives,
                "dtypes": list(self.dtypes),
                "n_traces": self.n_traces,
                "note": self.note}


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _sub_jaxprs(params: Dict[str, Any]):
    """Yield every jaxpr hiding in an equation's params (scan/while/cond
    bodies, pjit calls, custom transforms)."""
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for w in vs:
            inner = getattr(w, "jaxpr", None)
            if inner is not None:
                # ClosedJaxpr wraps .jaxpr; a plain Jaxpr has .eqns itself.
                yield inner if hasattr(inner, "eqns") else w


def _walk(jaxpr, in_loop: bool, prims: List[Tuple[str, bool]],
          dtypes: set) -> None:
    for eqn in jaxpr.eqns:
        prims.append((eqn.primitive.name, in_loop))
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                dtypes.add(str(aval.dtype))
        loop = in_loop or eqn.primitive.name in LOOP_PRIMITIVES
        for sub in _sub_jaxprs(eqn.params):
            _walk(sub, loop, prims, dtypes)


def jaxpr_summary(closed_jaxpr) -> Tuple[List[Tuple[str, bool]], set]:
    """Recursive (primitive name, inside-loop-body?) list + dtype set."""
    prims: List[Tuple[str, bool]] = []
    dtypes: set = set()
    for var in closed_jaxpr.jaxpr.invars:
        aval = getattr(var, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            dtypes.add(str(aval.dtype))
    _walk(closed_jaxpr.jaxpr, False, prims, dtypes)
    return prims, dtypes


#: dtypes wider than each ceiling (the contract fails if any appear).
_OVER_CEILING = {
    "float32": ("float64", "complex128"),
    "bfloat16": ("float32", "float64", "complex64", "complex128"),
    "float64": (),
}


def _check_hlo_line_ops(txt: str, needle: str) -> bool:
    """True when ``needle`` occurs as an HLO op token in the module text."""
    return needle in txt


# ---------------------------------------------------------------------------
# the checker
# ---------------------------------------------------------------------------

def check_contract(fn: Callable, args: Sequence[Any],
                   contract: CompilationContract,
                   kwargs: Optional[Dict[str, Any]] = None,
                   x64: bool = False,
                   static_argnums: Sequence[int] = (),
                   n_traces: Optional[int] = None) -> ContractReport:
    """Lower + compile ``fn(*args, **kwargs)`` once and verify ``contract``.

    ``fn`` may already be jitted (donation/sharding options are then part of
    what is checked) or a plain traceable callable (wrapped in a bare
    ``jax.jit``). Static operands go either through ``kwargs`` (when the
    jit declares ``static_argnames``) or positionally in ``args`` with
    their indices in ``static_argnums`` (when positional binding is forced,
    e.g. a jit carrying ``in_shardings``). ``x64=True`` runs the trace
    under ``jax.enable_x64`` — required for entry points whose
    semantics are float64 by design. ``n_traces`` threads an externally
    measured trace count (see :func:`count_traces`) into the
    ``max_traces`` check.
    """
    import jax

    kwargs = kwargs or {}
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)

    from contextlib import nullcontext

    ctx = jax.enable_x64() if x64 else nullcontext()
    with ctx:
        closed = jax.make_jaxpr(
            lambda *a: jitted(*a, **kwargs),
            static_argnums=tuple(static_argnums))(*args)
        lowered = jitted.lower(*args, **kwargs)
        hlo = lowered.compile().as_text()

    prims, dtypes = jaxpr_summary(closed)
    violations: List[ContractViolation] = []

    for needle in contract.forbidden_hlo:
        if _check_hlo_line_ops(hlo, needle):
            violations.append(ContractViolation(
                "forbidden_hlo", f"compiled HLO contains {needle!r}"))
    for needle in contract.required_hlo:
        if not _check_hlo_line_ops(hlo, needle):
            violations.append(ContractViolation(
                "required_hlo", f"compiled HLO is missing {needle!r}"))

    if contract.donation:
        # XLA records honored donations as input/output buffer aliases in
        # the module header; "input_output_alias={ {" only appears when at
        # least one alias entry exists.
        if "input_output_alias={ {" not in hlo:
            violations.append(ContractViolation(
                "donation", "no input_output_alias in the compiled module — "
                            "donate_argnums missing or not honored"))

    if contract.max_primitives is not None \
            and len(prims) > contract.max_primitives:
        top = ", ".join(f"{p}×{c}" for p, c in
                        Counter(p for p, _ in prims).most_common(5))
        violations.append(ContractViolation(
            "max_primitives",
            f"{len(prims)} primitives > budget {contract.max_primitives} "
            f"(top: {top})"))

    ceiling = contract.dtype_ceiling
    if ceiling is not None:
        over = set(_OVER_CEILING.get(ceiling, ())) & dtypes
        if over:
            violations.append(ContractViolation(
                "dtype_ceiling",
                f"dtypes {sorted(over)} exceed ceiling {ceiling!r}"))

    if contract.forbid_callbacks:
        for prim, in_loop in prims:
            if prim in CALLBACK_PRIMITIVES:
                where = "inside a scan/while body" if in_loop \
                    else "in the traced body"
                violations.append(ContractViolation(
                    "forbid_callbacks",
                    f"host callback primitive {prim!r} {where}"))

    if contract.max_traces is not None and n_traces is not None \
            and n_traces > contract.max_traces:
        violations.append(ContractViolation(
            "max_traces",
            f"{n_traces} traces > budget {contract.max_traces} — the jit "
            f"cache is growing per call (bucketing regression?)"))

    return ContractReport(
        name=contract.name, ok=not violations, violations=violations,
        n_primitives=len(prims),
        dtypes=tuple(sorted(dtypes)), n_traces=n_traces,
        note=contract.note)


# ---------------------------------------------------------------------------
# probes: how registry entries expose their hot paths
# ---------------------------------------------------------------------------

@dataclass
class ContractProbe:
    """One checkable (entry point, example args, contract) bundle.

    Registry entries attach zero-argument *factories* returning one of
    these (or a list of them); construction happens inside the factory so
    importing a backend module never builds engines or compiles anything.

    ``host_only=True`` marks entries with no compiled hot path (the pure
    NumPy reference oracles): they are still enumerated — every registered
    backend must expose a contract — but pass with a note instead of a
    lowering. ``traces`` optionally measures a recompile count for the
    contract's ``max_traces`` budget by driving the entry point through a
    canonical workload and reporting the jit-cache *growth* it causes
    (jax shares dispatch caches across jitted copies of one function, so
    growth — not absolute size — is the honest count; see
    :func:`count_traces`).
    """

    contract: CompilationContract
    fn: Optional[Callable] = None
    args: Tuple[Any, ...] = ()
    kwargs: Dict[str, Any] = field(default_factory=dict)
    x64: bool = False
    static_argnums: Tuple[int, ...] = ()
    host_only: bool = False
    note: str = ""
    traces: Optional[Callable[[], int]] = None


ProbeFactory = Callable[[], Union[ContractProbe, List[ContractProbe]]]


def host_probe(name: str, note: str) -> ContractProbe:
    """A passing probe for registry entries with no compiled hot path (the
    NumPy/scipy reference oracles). They still must be *enumerated* — every
    registered backend answers the contract checker — but there is nothing
    to lower."""
    return ContractProbe(contract=CompilationContract(name=name),
                         host_only=True, note=note)


def run_probe(probe: ContractProbe) -> ContractReport:
    """Check one probe; host-only probes pass with their note."""
    if probe.host_only:
        return ContractReport(name=probe.contract.name, ok=True,
                              note=probe.note or "host-only entry point "
                                                 "(no compiled hot path)")
    assert probe.fn is not None, "non-host probe needs an entry point"
    n_traces = probe.traces() if probe.traces is not None else None
    report = check_contract(probe.fn, probe.args, probe.contract,
                            kwargs=probe.kwargs, x64=probe.x64,
                            static_argnums=probe.static_argnums,
                            n_traces=n_traces)
    if probe.note and not report.note:
        report.note = probe.note
    return report


def count_traces(fn: Callable, arg_sets: Sequence[Tuple[Sequence[Any],
                                                        Dict[str, Any]]],
                 x64: bool = False, **jit_kwargs: Any) -> int:
    """Trace-cache *growth* of ``jax.jit(fn)`` driven over ``arg_sets``.

    Each element of ``arg_sets`` is ``(args, kwargs)``; the function is
    called once per element and the jit cache growth over the workload is
    the number of distinct traces it caused. Bucketing contracts assert
    this stays at the bucket count, not the call count.

    Growth, not absolute size: jax keys the dispatch cache on the
    underlying function plus the jit params, so a "fresh" ``jax.jit(fn)``
    wrapper still shares entries with every other jitted copy of ``fn`` in
    the process (e.g. a live engine's own dispatches, whose device-sharded
    argument layouts occupy separate cache slots). The baseline is read
    before the workload runs so only workload-caused traces are counted.
    """
    import jax

    from contextlib import nullcontext

    jitted = jax.jit(fn, **jit_kwargs)
    base = int(jitted._cache_size())
    with (jax.enable_x64() if x64 else nullcontext()):
        for args, kwargs in arg_sets:
            jitted(*args, **kwargs)
    return int(jitted._cache_size()) - base

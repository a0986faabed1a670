"""The designer-compute program registry.

One process-wide table, two indexes:

- **by kind** — ``get("gp_ucb_pe")`` → the :class:`~vizier_tpu.compute.ir.
  DesignerProgram` whose device body executes that bucket family; tools
  (obs_report, run stamps) enumerate :func:`kinds` instead of maintaining
  hardcoded lists.
- **by designer type** — :func:`resolve` walks ``type(designer).__mro__``
  to the most-derived class with registered programs and returns the
  first program whose ``bucket_key`` accepts the designer's current state
  (e.g. the exact GP-bandit program declines a study the surrogate
  auto-switch has flipped sparse, and the sparse program picks it up).

A wrapper composes without registering: a designer exposing
``compute_program(count) -> (program, key) | None`` overrides resolution
entirely — the chaos harness uses this to wrap the resolved program in
fault-injecting hooks. A designer with neither a hook nor a registered
program resolves to ``None`` and is served by its sequential ``suggest``.

Registration happens at designer-module import; :func:`kinds` and
:func:`programs` import the in-tree designer modules themselves. The
analysis suite's ``compute_ir`` pass statically audits every
``register(...)`` site for prewarm coverage and chaos-test coverage.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

from vizier_tpu.compute import ir

_LOCK = threading.Lock()
_BY_KIND: Dict[str, ir.DesignerProgram] = {}
_BY_TYPE: Dict[type, List[ir.DesignerProgram]] = {}


def register(designer_type: type, program: ir.DesignerProgram) -> ir.DesignerProgram:
    """Adds ``program`` for designers of ``designer_type`` (idempotent:
    re-registering the same kind replaces it — module reloads in tests)."""
    if not program.kind:
        raise ValueError(f"{type(program).__name__} must declare a kind.")
    with _LOCK:
        existing = _BY_KIND.get(program.kind)
        if existing is not None:
            # Replace in both indexes (same-kind re-registration only).
            for programs in _BY_TYPE.values():
                programs[:] = [p for p in programs if p.kind != program.kind]
        _BY_KIND[program.kind] = program
        _BY_TYPE.setdefault(designer_type, []).append(program)
    return program


def get(kind: str) -> Optional[ir.DesignerProgram]:
    with _LOCK:
        return _BY_KIND.get(kind)


def kinds() -> Tuple[str, ...]:
    """Registered program kinds, sorted (stable for stamps/reports)."""
    _ensure_builtin_programs()
    with _LOCK:
        return tuple(sorted(_BY_KIND))


def programs() -> Tuple[ir.DesignerProgram, ...]:
    _ensure_builtin_programs()
    with _LOCK:
        return tuple(_BY_KIND[k] for k in sorted(_BY_KIND))


def programs_for_algorithm(algorithm: str) -> Tuple[ir.DesignerProgram, ...]:
    """Programs a service prewarm for ``algorithm`` should compile."""
    return tuple(p for p in programs() if p.matches_algorithm(algorithm))


def _ensure_builtin_programs() -> None:
    """Imports the in-tree designer modules so their programs are present.

    Resolution by designer type works without this (importing a designer
    class imports its module, which registers); only whole-registry
    enumeration (kinds/programs, the prewarm walk, stamps) needs the full
    set eagerly.
    """
    import vizier_tpu.designers.gp_bandit  # noqa: F401  (registers on import)
    import vizier_tpu.designers.gp_ucb_pe  # noqa: F401


def resolve(
    designer: Any, count: Optional[int] = None
) -> Optional[Tuple[ir.DesignerProgram, ir.BucketKey]]:
    """The designer's program + bucket key for this compute, or None.

    Order: the designer's own ``compute_program`` hook (wrappers), then
    the most-derived registered designer type's programs in registration
    order (first non-None ``bucket_key`` wins). None means unbatchable —
    the caller runs the plain sequential ``suggest``.
    """
    count = count or 1
    hook = getattr(designer, "compute_program", None)
    if hook is not None:
        return hook(count)
    with _LOCK:
        type_programs: List[ir.DesignerProgram] = []
        for cls in type(designer).__mro__:
            found = _BY_TYPE.get(cls)
            if found:
                type_programs = list(found)
                break
    for program in type_programs:
        key = program.bucket_key(designer, count)
        if key is not None:
            return program, key
    return None

"""JAX-aware phase timing: the ``device.wait`` stage span.

JAX dispatch is asynchronous — wall-clocking a jitted call measures
*enqueue*, not device work, and the cost silently lands on whatever later
op first blocks. :func:`device_phase` wraps a designer hot-path stage in
the ``device.wait`` stage span (``observability/tracing.py``) and has the
caller ``block()`` the stage's outputs *inside* it, so the time the host
spends blocked on the chip is attributed to the right phase:

    with jax_timing.device_phase("gp_ucb_pe.train_gp", stage="train") as phase:
        states, work = self._train(...)
        phase.ahead(work)        # its counts start for the host now
        sweeps = self._dispatch_sweeps(states, ...)  # enqueued behind the train
        phase.block(states)
        counts = phase.read(work)  # a copy that has already arrived
    with jax_timing.device_phase("gp_ucb_pe.acquisition", stage="acquire"):
        jax.block_until_ready(sweeps)

**Every program of a suggest is enqueued before the host waits for any of
them** (``designers/gp_ucb_pe.py`` ``suggest``, PR 45): a block only reads a
clock, it never stands between two launches. So a train's span runs from the
train's dispatch to the train's END and covers the sweeps' launches (their
host-to-device copies, and on a process's first call their trace and compile),
which cost the chip nothing while the train runs; the acquire span that follows
is what is left of the sweeps on the chip after the train has ended — not the
sweeps' launches, and not a measure of how fast a sweep is by itself. A suggest
that trains nothing (a cached fit) has no train to hide its launches behind:
its acquire span holds them, as every acquire span did before PR 45.

The span's attributes say which phase it was: ``phase`` (the name given
here — for a flush program, its ``DesignerProgram.device_phase``), ``stage``
(``train`` / ``acquire`` on the sequential path), ``path`` (``sequential`` /
``fused``), ``per`` (``flush`` for a fused flush's one wait), ``devices``
(the width of the designer mesh the phase's programs were partitioned over;
1 without one), ``first_call`` and ``mode`` — the first occurrence of a
phase name in the process is ``mode="compile"`` (trace + lower + compile dominates it), later ones
``mode="execute"``, the steady-state serving number. Like every stage span
it is observed into ``vizier_suggest_stage_seconds{stage="device.wait",...}``
in the serving runtime's registry — there under the label ``phase``, which is
this span's ``stage`` (``train`` / ``acquire``) or ``flush`` for a fused
flush's one wait — and annotates the ``jax.profiler`` trace. A train phase
also carries what its program counted of its own work (``phase.read`` after
the block, ``phase.set_attributes``): ``loop_trips``, ``rows``,
``row_iterations``, ``evaluations``; and, where it sent its sweeps ahead,
``sweeps_ahead`` (1: the train was still running when the last sweep was
enqueued — ``is_ready()``, a poll).

With observability (or the JAX knob) off, the phase object is inert and —
deliberately — does NOT ``block_until_ready`` and reads nothing: the
production path keeps JAX's async pipelining, so the off switch costs nothing.
"""

from __future__ import annotations

import threading
from typing import Any, Optional, Set

from vizier_tpu.observability import config as config_lib
from vizier_tpu.observability import tracing as tracing_lib

_seen_lock = threading.Lock()
_seen_phases: Set[str] = set()

_config: Optional[config_lib.ObservabilityConfig] = None


def _jax_profiling_on() -> bool:
    global _config
    if _config is None:
        _config = config_lib.ObservabilityConfig.from_env()
    return _config.jax_profiling_on


def set_config(config: Optional[config_lib.ObservabilityConfig]) -> None:
    """Overrides the env-derived config (tests); None re-reads on next use."""
    global _config
    _config = config


def reset_compile_tracking() -> None:
    """Forgets which phases have run (tests)."""
    with _seen_lock:
        _seen_phases.clear()


def _mark_seen(name: str) -> bool:
    """True iff this is the first time ``name`` runs in this process."""
    with _seen_lock:
        if name in _seen_phases:
            return False
        _seen_phases.add(name)
        return True


class _Phase:
    """Yielded by :func:`device_phase`; ``block()`` pins device time here."""

    __slots__ = ("name", "enabled", "first_call", "_span")

    def __init__(self, name: str, enabled: bool, first_call: bool):
        self.name = name
        self.enabled = enabled
        self.first_call = first_call
        self._span: Any = None  # the open ``device.wait`` span

    def block(self, outputs: Any) -> Any:
        """``jax.block_until_ready`` on ``outputs`` (pytree-ok), returned
        unchanged. No-op — keeping async dispatch — when profiling is off."""
        if self.enabled:
            import jax

            jax.block_until_ready(outputs)
        return outputs

    def ahead(self, small: Any) -> None:
        """Starts the device-to-host copy of what ``read`` will fetch, when
        its program is dispatched: the read after the block then waits for
        nothing. Asks for nothing when profiling is off."""
        if self.enabled:
            import jax

            for leaf in jax.tree_util.tree_leaves(small):
                leaf.copy_to_host_async()

    def read(self, small: Any) -> Any:
        """ONE device-to-host read of a small output the phase has blocked
        on — what its program counted of its own work — as NumPy. None, and
        nothing read, when profiling is off."""
        if not self.enabled:
            return None
        import jax

        return jax.device_get(small)

    def set_attributes(self, **attributes: Any) -> None:
        """Attributes of the phase's ``device.wait`` span (what ``read``
        found: the ring and the span log then show it per request)."""
        if self._span is not None:
            for key, value in attributes.items():
                self._span.set_attribute(key, value)


_DISABLED_PHASE = _Phase("", enabled=False, first_call=False)


class _PhaseCM:
    __slots__ = ("_phase", "_span_cm")

    def __init__(
        self, phase: _Phase, path: str, per: str, stage: Optional[str], devices: int
    ):
        self._phase = phase
        attributes = {"stage": stage} if stage else {}
        self._span_cm = tracing_lib.get_tracer().span(
            tracing_lib.DEVICE_WAIT,
            phase=phase.name,
            path=path,
            per=per,
            devices=devices,
            first_call=phase.first_call,
            mode="compile" if phase.first_call else "execute",
            **attributes,
        )

    def __enter__(self) -> _Phase:
        self._phase._span = self._span_cm.__enter__()
        return self._phase

    def __exit__(self, exc_type, exc, tb) -> bool:
        return self._span_cm.__exit__(exc_type, exc, tb)


class _DisabledPhaseCM:
    __slots__ = ()

    def __enter__(self) -> _Phase:
        return _DISABLED_PHASE

    def __exit__(self, *exc) -> bool:
        return False


_DISABLED_CM = _DisabledPhaseCM()


def device_phase(
    name: str,
    *,
    path: str = tracing_lib.PATH_SEQUENTIAL,
    per: str = tracing_lib.PER_REQUEST,
    stage: Optional[str] = None,
    devices: int = 1,
):
    """Times one device phase (see module docstring for the contract)."""
    if not _jax_profiling_on():
        return _DISABLED_CM
    phase = _Phase(name, enabled=True, first_call=_mark_seen(name))
    return _PhaseCM(phase, path, per, stage, devices)

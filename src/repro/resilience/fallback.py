"""Engine fallback chains and retry/deadline helpers.

The LOOPS design always has a slower-but-correct way to run any matrix —
ultimately the jnp oracle the whole test suite is pinned against.  This
module encodes that as an explicit per-``(part, op)`` **fallback chain**

    pallas → interpret → jnp

walked by :func:`run_chain`: the engine entry points wrap each backend's
dispatch in an ``attempt(backend)`` closure, and a failing attempt degrades
to the next link with an ``engine.fallback{part,op,reason}`` counter instead
of letting the exception escape ``loops_spmm``.  The fused single-pass
kernel has no jnp equivalent, so its chain ends at ``interpret`` and
``core.spmm._loops_execute`` catches the exhausted chain and re-runs the
two-pass parts path (each part then owns its own chain down to the oracle).

Fallback fires at trace time when the failure does (kernel lowering and
interpret-mode faults raise during tracing), so under ``jax.jit`` a degraded
call compiles the fallback backend — the counter is per-compilation, like
every engine dispatch counter.

Kill switch: ``REPRO_NO_FALLBACK=1`` (or the :func:`disabled` context
manager) makes every chain single-link so failures propagate — tests that
assert error behaviour, and operators who prefer crash-fast, use this.

On a TPU the chain never leaves ``pallas``: a refused or failing kernel
raises (:func:`degrades`).  Degrading there would quietly swap the chip's
kernels for the interpreter or the jnp oracle; the chains exist for the CPU
and for the chaos tests.

:func:`retry_with_backoff` is the host-side half: transient *step* failures
(serving/training) retry with exponential backoff under an optional
deadline.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Callable, Dict, Tuple

from .inject import (InjectedFault, InjectedTimeout, fault_point,
                     note_degraded)

__all__ = ["DEFAULT_CHAIN", "FallbackPolicy", "get_policy", "set_policy",
           "disabled", "degrades", "on_tpu", "pinned", "run_chain",
           "classify",
           "retry_with_backoff", "DeadlineExceeded"]

# The canonical degradation order: fastest first, oracle last.
DEFAULT_CHAIN: Tuple[str, ...] = ("pallas", "interpret", "jnp")

# Per-(part, op) overrides.  The fused kernel is Pallas-only (it relies on
# input_output_aliases); its chain ends at interpret and the caller
# (core.spmm._loops_execute) degrades to the two-pass parts path.
CHAIN_OVERRIDES: Dict[Tuple[str, str], Tuple[str, ...]] = {
    ("fused", "spmm"): ("pallas", "interpret"),
}


@dataclasses.dataclass
class FallbackPolicy:
    """Which chain each ``(part, op)`` walks, and whether chains are live."""

    enabled: bool = True
    chains: Dict[Tuple[str, str], Tuple[str, ...]] = dataclasses.field(
        default_factory=lambda: dict(CHAIN_OVERRIDES))

    def chain_for(self, part: str, op: str, backend: str) -> Tuple[str, ...]:
        """The chain starting at the caller's resolved ``backend`` — a
        caller already on a degraded link never climbs back up."""
        if not self.enabled or pinned(backend):
            return (backend,)
        chain = self.chains.get((part, op), DEFAULT_CHAIN)
        if backend in chain:
            return chain[chain.index(backend):]
        return (backend,)


def on_tpu() -> bool:
    """The platform probe: is JAX's default backend a TPU?"""
    import jax
    return jax.default_backend() == "tpu"


def pinned(backend: str) -> bool:
    """Is ``backend`` the chip's own kernels — ``pallas`` on a TPU — whose
    failures must raise rather than be skipped or degraded?"""
    return backend == "pallas" and on_tpu()


def degrades(backend: str) -> bool:
    """May a failure on ``backend`` degrade to a slower path?  Not with
    the kill switch on, and never when the backend is :func:`pinned`."""
    return get_policy().enabled and not pinned(backend)


_POLICY = FallbackPolicy(
    enabled=os.environ.get("REPRO_NO_FALLBACK", "") not in ("1", "true"))


def get_policy() -> FallbackPolicy:
    return _POLICY


def set_policy(policy: FallbackPolicy) -> FallbackPolicy:
    """Install ``policy`` process-wide; returns the previous one."""
    global _POLICY
    prev, _POLICY = _POLICY, policy
    return prev


@contextlib.contextmanager
def disabled():
    """Temporarily make every chain single-link (failures propagate) —
    the test-facing form of ``REPRO_NO_FALLBACK``."""
    prev = set_policy(FallbackPolicy(enabled=False))
    try:
        yield
    finally:
        set_policy(prev)


def classify(exc: BaseException) -> str:
    """Compact counter-label reason for a failure."""
    if isinstance(exc, InjectedTimeout):
        return "timeout"
    if isinstance(exc, InjectedFault):
        return "injected"
    if isinstance(exc, TimeoutError):
        return "timeout"
    return type(exc).__name__


def run_chain(part: str, op: str, backend: str,
              attempt: Callable[[str], object], *, site: str | None = None):
    """Walk the ``(part, op)`` chain from ``backend``: call
    ``attempt(link)`` per link, degrading on any exception with an
    ``engine.fallback`` counter; re-raise the last failure when the chain
    is exhausted.  Each attempt passes through a
    ``engine.{part}.{op}.{link}`` fault point first (the chaos harness
    fails *attempts*, so an injected first-link fault proves the
    degradation end-to-end)."""
    site = site or f"engine.{part}.{op}"
    chain = get_policy().chain_for(part, op, backend)
    last_exc: BaseException | None = None
    for i, link in enumerate(chain):
        if i:
            note_degraded("engine.fallback", part=part, op=op,
                          reason=classify(last_exc))
        try:
            fault_point(f"{site}.{link}")
            return attempt(link)
        except Exception as e:        # noqa: BLE001 - the chain IS the handler
            last_exc = e
    raise last_exc


class DeadlineExceeded(TimeoutError):
    """A retried call ran out of its deadline budget."""


def retry_with_backoff(fn: Callable, *args, retries: int = 2,
                       backoff_s: float = 0.01, deadline_s: float | None = None,
                       on_retry: Callable[[int, BaseException], None] | None
                       = None, **kwargs):
    """Call ``fn(*args, **kwargs)``; on failure retry up to ``retries``
    times with exponential backoff (``backoff_s`` doubling per attempt).

    ``deadline_s`` bounds the *total* wall clock: a retry that cannot start
    before the deadline raises :class:`DeadlineExceeded` from the last
    failure instead of sleeping past it.  ``on_retry(attempt, exc)`` fires
    before each backoff sleep — the serving driver counts degradations
    there.
    """
    t0 = time.perf_counter()
    delay = backoff_s
    attempt = 0
    while True:
        try:
            return fn(*args, **kwargs)
        except Exception as e:        # noqa: BLE001 - retry IS the handler
            attempt += 1
            if attempt > retries:
                raise
            if deadline_s is not None \
                    and time.perf_counter() - t0 + delay > deadline_s:
                raise DeadlineExceeded(
                    f"deadline {deadline_s:.3f}s exhausted after "
                    f"{attempt} attempt(s)") from e
            if on_retry is not None:
                on_retry(attempt, e)
            time.sleep(delay)
            delay *= 2

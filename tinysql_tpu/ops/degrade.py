"""Runtime device-loss degradation.

The planner's device enforcer promises a CPU fallback (ROADMAP north
star); this module supplies the RUNTIME half: when a compiled-program
dispatch or a device->host transfer dies mid-statement because the
accelerator or the connection to it went away (raised by jax as a
``JaxRuntimeError`` whose status is one of :data:`_LOSS_STATUSES`, or
injected via the ``kernelDispatchError``/``kernelD2HError`` failpoints
raising :class:`DeviceLost`), the session

1. records the loss (counters below, exported to /metrics),
2. pins planning to the CPU tier for a cooldown window
   (``tidb_device_cooldown`` seconds; every ``Session._use_tpu`` read
   consults :func:`cpu_pinned`), and
3. transparently re-executes the statement once on the CPU volcano
   path — READ-ONLY statements only; writes surface the error, because
   a re-run after a partially-dispatched write is not idempotent.

Detection is by what the error SAYS, not by its type.  jax raises the
same ``JaxRuntimeError`` when the compiler refuses a program
(``INTERNAL``, ``INVALID_ARGUMENT``), when device memory is exhausted
(``RESOURCE_EXHAUSTED``) and when a lowering does not exist
(``UNIMPLEMENTED``): those are defects of one program, they fail the
statement loudly, and they never demote the process to CPU — as a
TypeError from a kernel bug never did.
"""
from __future__ import annotations

import threading
import time

DEFAULT_COOLDOWN_S = 30.0

#: status codes (the leading word of a jax runtime error's message) that
#: say the device or the connection to it went away.  Every other status
#: is a statement error.
_LOSS_STATUSES = ("UNAVAILABLE", "DEADLINE_EXCEEDED")


class DeviceLost(RuntimeError):
    """Raised (or injected) at the dispatch/transfer boundary when the
    accelerator vanished mid-statement."""


_mu = threading.Lock()
_pinned_until = 0.0
_losses = 0
_degraded_statements = 0


#: failpoints that sit ON the device boundary: a generic Injected error
#: from them models the accelerator dying (spec strings cannot name an
#: exception class, so `tidb_failpoints='kernelDispatchError=error(x)'`
#: must degrade exactly like a programmatic DeviceLost)
_DEVICE_FAILPOINTS = ("kernelDispatchError", "kernelD2HError")


def is_device_loss(exc: BaseException) -> bool:
    """True for :class:`DeviceLost`, for an injected error of a failpoint
    ON the device boundary, and for a jax runtime error whose status is
    in :data:`_LOSS_STATUSES`.  A refusal to compile, an exhausted
    resource, an invalid argument or an unimplemented operation is NOT a
    lost device: the statement fails with it."""
    from jax.errors import JaxRuntimeError
    for e in (exc, exc.__cause__, exc.__context__):
        if e is None:
            continue
        if isinstance(e, DeviceLost):
            return True
        if getattr(e, "failpoint", None) in _DEVICE_FAILPOINTS:
            return True
        if isinstance(e, JaxRuntimeError) \
                and str(e).split(":", 1)[0].strip() in _LOSS_STATUSES:
            return True
    return False


def record_loss(cooldown_s: float = DEFAULT_COOLDOWN_S) -> None:
    """One observed device loss: bump counters, open/extend the CPU pin
    window."""
    global _pinned_until, _losses
    until = time.monotonic() + max(0.0, float(cooldown_s))
    with _mu:
        _losses += 1
        _pinned_until = max(_pinned_until, until)
    try:
        from ..obs import context as _obs
        _obs.record("device_loss", 1)
    except Exception:
        pass


def record_degraded_statement() -> None:
    global _degraded_statements
    with _mu:
        _degraded_statements += 1


def cpu_pinned() -> bool:
    with _mu:
        return time.monotonic() < _pinned_until


def snapshot() -> dict:
    with _mu:
        return {"device_loss_total": _losses,
                "degraded_statements_total": _degraded_statements,
                "cpu_pinned": 1 if time.monotonic() < _pinned_until else 0}


def reset() -> None:
    """Tests only."""
    global _pinned_until, _losses, _degraded_statements
    with _mu:
        _pinned_until = 0.0
        _losses = 0
        _degraded_statements = 0

"""Test env: tests force JAX onto the CPU, as a virtual 8-device mesh, so
the multi-device sharding paths run without an accelerator and results
are bit-deterministic.  (The program itself takes whatever device JAX
finds; only the tests pin one.)
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# TINYSQL_RACE_STRESS: arm the dynamic concurrency verifier BEFORE any
# tinysql_tpu module is imported — module-level locks must come out of
# the instrumented constructors or the guard audit cannot see them
# (tools/race_stress.py drives this; utils/racestress.py implements it)
_RACE_STRESS = os.environ.get("TINYSQL_RACE_STRESS")
if _RACE_STRESS:
    # load by FILE PATH, not package import: `import tinysql_tpu.utils`
    # would pull failpoint -> fail and create fail._mu with the RAW
    # constructor before install() could patch it
    import importlib.util as _ilu
    import sys as _sys
    _rs_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tinysql_tpu", "utils", "racestress.py")
    _spec = _ilu.spec_from_file_location(
        "tinysql_tpu.utils.racestress", _rs_path)
    _racestress = _ilu.module_from_spec(_spec)
    _sys.modules["tinysql_tpu.utils.racestress"] = _racestress
    _spec.loader.exec_module(_racestress)
    _racestress.install()
    _racestress.audit_known()

# TINYSQL_XFER_AUDIT: arm the dynamic transfer verifier BEFORE any
# tinysql_tpu module is imported — the interposed jnp.asarray/device_get
# must be in place when kernels first resolves them (tools/
# transfer_audit.py drives this; utils/xferaudit.py implements it).
# Same file-path load as racestress: a package import would construct
# engine module state before install() runs.
_XFER_AUDIT = os.environ.get("TINYSQL_XFER_AUDIT")
if _XFER_AUDIT:
    import importlib.util as _ilu
    import sys as _sys
    _xa_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tinysql_tpu", "utils", "xferaudit.py")
    _spec = _ilu.spec_from_file_location(
        "tinysql_tpu.utils.xferaudit", _xa_path)
    _xferaudit = _ilu.module_from_spec(_spec)
    _sys.modules["tinysql_tpu.utils.xferaudit"] = _xferaudit
    _spec.loader.exec_module(_xferaudit)
    _xferaudit.install()


import contextlib
import threading as _threading
import time as _time

import pytest as _pytest

import _timelimit

_REAL_STDERR = 2
_RUNNING = None  # under xdist: this worker's _timelimit.Running


def pytest_configure(config):
    """Capture is suspended here, so fd 2 is still the process's own
    stderr: keep a copy for the hard limit, whose process ends before any
    captured output could be reported."""
    global _REAL_STDERR, _RUNNING
    _REAL_STDERR = os.dup(2)
    worker = getattr(config, "workerinput", None)
    if worker is not None:
        _RUNNING = _timelimit.Running(worker["testrunuid"],
                                      worker["workerid"])


@_pytest.hookimpl(hookwrapper=True, tryfirst=True)
def pytest_runtest_protocol(item, nextitem):
    """Every item runs under `_timelimit.LIMIT_S`, its fixtures' set-up
    and tear-down included (a module fixture's are inside the limit of the
    item that runs them)."""
    named = _RUNNING.item(item.nodeid) if _RUNNING is not None \
        else contextlib.nullcontext()
    with named, _timelimit.limited(_timelimit.LIMIT_S, item.nodeid,
                                   log=_REAL_STDERR) as item.limit_fired:
        yield


@_pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """An item whose limit fired fails even where what the alarm raised
    was swallowed (it interrupted a finalizer's wait, say) and the phase
    went on to pass: 120 s lost in silence is the fault to report."""
    rep = (yield).get_result()
    if rep.passed and getattr(item, "limit_fired", None):
        rep.outcome = "failed"
        rep.longrepr = (f"{item.nodeid}: the time limit fired during "
                        f"{rep.when} and what it raised was swallowed; "
                        "every thread's stack is on stderr")
        item.limit_fired.clear()  # one failed report an item


@_pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    if _RUNNING is not None and _RUNNING.ended_a_worker(item.nodeid):
        _pytest.fail("this item ended an earlier worker (its stacks are on "
                     "that worker's stderr); not run again", pytrace=False)


def pytest_sessionfinish(session, exitstatus):
    """Race-stress mode publishes its lock-contention / unguarded-write
    report at session end (the CI job uploads it as an artifact)."""
    if _RACE_STRESS:
        path = os.environ.get("TINYSQL_RACE_STRESS_REPORT")
        if path:
            _racestress.write_report(path)
    if _XFER_AUDIT:
        path = os.environ.get("TINYSQL_XFER_AUDIT_REPORT")
        if path:
            _xferaudit.write_report(path)


@_pytest.fixture(autouse=True, scope="module")
def _no_thread_leak_per_module():
    """Per-suite leak discipline (reference: util/testleak AfterTest wired
    into every suite, leaktest.go:118): no non-daemon thread created in a
    test module may survive the module."""
    def live():
        return [t for t in _threading.enumerate()
                if t is not _threading.main_thread()
                and not t.daemon and t.is_alive()]
    # strong refs to baseline Thread OBJECTS: comparing by id() would let
    # a leaked thread hide behind a recycled address of a dead baseline
    base = list(live())
    yield
    deadline = _time.time() + 3.0
    extra = [t for t in live() if t not in base]
    while extra and _time.time() < deadline:
        _time.sleep(0.05)
        extra = [t for t in live() if t not in base]
    assert not extra, \
        f"module leaked non-daemon threads: {sorted(t.name for t in extra)}"


@_pytest.fixture
def four_devices(monkeypatch):
    """A host of four devices, as the four-chip cells' is: the session's
    mesh, the planner's price of a broadcast (four copies) and the count
    of whole-mesh dispatches all see the first four of the eight host
    devices the tests force."""
    from tinysql_tpu.parallel import dist
    found = jax.devices
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **kw: found(*a, **kw)[:4])
    monkeypatch.setattr(dist, "_SESSION_MESH", None)

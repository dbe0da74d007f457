#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the SQL path runs on the chip.

One process, no environment needed: ``python chip_smoke.py`` from the root
of the tree.  It drives the system's main path once through the entry
points a user would call — TPC-H (``--sf``, default 1, generated from
``--seed``) bulk-loaded into a store, the real wire ``Server`` on port 0
in this process, and the repo's wire client talking to it over the
socket — and checks every answer against sqlite over the same data.

It sets no ``JAX_PLATFORMS``, starts no child that touches JAX, and
fails unless the device JAX found is the one expected (``tpu``;
``--expect-platform cpu`` exists for the CPU rehearsal and the tier-1
test only).  Each phase prints one JSON line as it ends; the LAST line of
standard output is ``{"ok": ..., "device": {...}}`` with the device as
``jax.devices()`` reports it.  Exit code 0 only when every phase passed
and nothing hid the device: every statement dispatched compiled programs
and no numpy twin, no device loss, no degraded statement, no WARNING on
the ``tinysql_tpu`` logger, and the tables' bytes were on the device.

``--mesh`` (four chips, run by hand) runs the device and load phases and
then only Q1 and Q3 under ``tidb_mesh_parallel = 1`` beside the same two
on one device.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import logging
import os
import subprocess
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
# tests/: the wire client the repo already has (test_server.MiniClient)
for _p in (os.path.join(ROOT, "tests"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

QUERY_NAMES = ("Q1", "Q3", "Q6")
STATEMENT_TIMEOUT_S = 1100.0
#: numeric lineitem columns Q1/Q3/Q6 read: their bytes must have been on
#: the device for the tables to "live on the chip"
LINEITEM_NUMERIC_READ = ("l_orderkey", "l_quantity", "l_extendedprice",
                         "l_discount", "l_tax")
#: scratch table of the write-then-read phase: above tidb_tpu_min_rows
#: (8192), so its aggregate is placed on the device
SCRATCH_ROWS = 20_000
SCRATCH_BATCH = 1_000
SCRATCH_AGG = ("select g, count(*), sum(v), max(v) from smoke_w "
               "group by g order by g")
SCRATCH_UPDATE = "update smoke_w set v = v + 0.5 where id <= 5000"


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


class _LogWatch(logging.Handler):
    """Sees every WARNING-or-worse record below the ``tinysql_tpu``
    logger.  Records of the logger named EXACTLY ``tinysql_tpu`` fail
    the run: that is where the fused pipeline's bail-out, a skipped
    prewarm compile and the degraded re-run speak.  Child loggers
    (``tinysql_tpu.slowlog`` logs every slow statement at WARNING, and a
    cold SF=1 query is slow) are printed, not failed on."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.failing = []

    def emit(self, record):
        msg = self.format(record)
        if record.name == "tinysql_tpu":
            self.failing.append(msg)
        print(f"[log {record.levelname} {record.name}] {msg}",
              file=sys.stderr, flush=True)


def _rss_mib() -> float:
    with open("/proc/self/statm") as f:
        pages = int(f.read().split()[1])
    return round(pages * os.sysconf("SC_PAGE_SIZE") / 2 ** 20, 1)


def _line(phase: str, t0: float, **fields) -> None:
    print(json.dumps({"phase": phase,
                      "seconds": round(time.time() - t0, 3),
                      "rss_mib": _rss_mib(), **fields}),
          flush=True)


def _require(checks: dict, where: str) -> None:
    bad = sorted(k for k, ok in checks.items() if not ok)
    if bad:
        raise SmokeFailure(f"{where}: failed checks {bad}")


# ---- phases ---------------------------------------------------------------

def phase_device() -> dict:
    """Before any data is made: which device did JAX find?"""
    t0 = time.time()
    from importlib import metadata
    import jax
    import jaxlib
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    from tinysql_tpu.ops import kernels
    kernels.jax()  # the engine's one-time jax configuration (x64, cache)
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    _line("device", t0, **device, jax=jax.__version__,
          jaxlib=jaxlib.__version__, libtpu=libtpu,
          python=sys.version.split()[0],
          compile_cache_dir=jax.config.jax_compilation_cache_dir,
          compile_cache_env=bool(os.environ.get(kernels.CACHE_DIR_ENV)))
    return device


def expect_device(device: dict, platform: str, count: int) -> None:
    """There is no path on which the run carries on without the chip."""
    if device["platform"] != platform:
        raise SmokeFailure(f"expected platform {platform!r}, jax found "
                           f"{device['platform']!r}")
    if device["count"] < count:
        raise SmokeFailure(f"need {count} devices, jax found "
                           f"{device['count']}")


def _build_native() -> str:
    """Rebuild native/libtinysql_native.so from its source: the .so is
    git-ignored, so one that is already there came with the copy and is
    not trusted.  tinysql_tpu/native.py falls back to pure Python in
    silence; this says which it was."""
    spec = importlib.util.spec_from_file_location(
        "tsnative_build", os.path.join(ROOT, "native", "build.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    try:
        mod.build()
        built = True
    except (OSError, subprocess.CalledProcessError) as e:
        print(f"[smoke] native build failed: {e}", file=sys.stderr)
        built = False
    from tinysql_tpu import native
    if native.lib() is None:
        return "unavailable"
    return "built" if built else "found"


class _Loaded:
    """What the load phase leaves for the later ones (closed by main)."""

    def __init__(self):
        self.data = None
        self.storage = None
        self.server = None
        self.client = None
        self.mirror = None

    def close(self) -> None:
        if self.client is not None:
            self.client.close()
        if self.server is not None:
            self.server.close()
        if self.storage is not None:
            self.storage.close()
        if self.mirror is not None:
            self.mirror.close()


def phase_load(sf: float, seed: int, env: _Loaded) -> None:
    t0 = time.time()
    from test_server import MiniClient
    from tinysql_tpu.bench import tpch
    from tinysql_tpu.kv import new_mock_storage
    from tinysql_tpu.server.server import Server
    from tinysql_tpu.session.session import Session
    native_lib = _build_native()
    env.data = tpch.generate(sf, seed)
    t_gen = time.time() - t0
    env.storage = new_mock_storage()
    boot = Session(env.storage)
    counts = tpch.load(boot, sf=sf, seed=seed, data=env.data)
    # the one sysvar the smoke changes: from its first 60 s cycle on, the
    # auto-prewarm worker re-runs the hottest statements in the
    # background, which would move the per-statement counters and the
    # warm runs' compile count this script asserts on.  Placement,
    # thresholds and the fused-pipeline gate keep their defaults.
    boot.execute("set global tidb_auto_prewarm = 0")
    # the reference is built before the server starts; the order is free
    # (the server's heap profiler traces 10 ms of a second, so a
    # Python-heavy load beside a running server is not slowed)
    t1 = time.time()
    env.mirror = tpch.sqlite_mirror(env.data)
    t_mirror = time.time() - t1
    env.server = Server(env.storage, port=0)
    env.server.start()
    env.client = MiniClient(env.server.port, db="tpch")
    # a cold SF=1 statement compiles for minutes; the client's 10 s
    # default is for tests.  The run as a whole is bounded by its caller.
    env.client.sock.settimeout(STATEMENT_TIMEOUT_S)
    _line("load", t0, sf=sf, seed=seed, rows=counts, native_lib=native_lib,
          generate_s=round(t_gen, 3), sqlite_mirror_s=round(t_mirror, 3),
          server_port=env.server.port)


def _run_counted(client, sql: str):
    """One statement over the wire with the engine's device counters
    around it (the server lives in this process)."""
    from tinysql_tpu.ops import kernels
    snap = kernels.stats_snapshot()
    t0 = time.time()
    _cols, rows = client.query(sql)
    dt = time.time() - t0
    d = kernels.stats_delta(snap)
    return rows, round(dt, 4), {
        k: int(d.get(k, 0)) for k in
        ("dispatches", "host_dispatches", "h2d_transfers", "h2d_bytes",
         "d2h_transfers", "d2h_bytes", "progcache_misses",
         "mesh_dispatches", "reshard_bytes")}


def _rows_match(a, b, rel=1e-6) -> bool:
    if len(a) != len(b):
        return False
    def canon(rows):
        out = []
        for r in rows:
            key = []
            for v in r:
                if isinstance(v, float):
                    key.append(f"{(0.0 if v == 0 else v):.9g}")
                else:
                    key.append(str(v))
            out.append(tuple(key))
        return sorted(out)
    ca, cb = canon(a), canon(b)
    for ra, rb in zip(ca, cb):
        for va, vb in zip(ra, rb):
            if va == vb:
                continue
            try:
                fa, fb = float(va), float(vb)
            except ValueError:
                return False
            if abs(fa - fb) > rel * max(1.0, abs(fa), abs(fb)):
                return False
    return True


def _query_runs(client, mirror, name: str, sql: str, runs) -> tuple:
    """EXPLAIN shows device placement; then one run per label in
    ``runs``: rows equal sqlite's, compiled programs dispatched and no
    numpy twin.  Returns (fields of the line, checks, the last run's
    rows)."""
    _cols, plan = client.query("explain " + sql)
    placed = [r[0].strip() for r in plan if r[2] == "tpu"]
    want = [list(r) for r in mirror.execute(sql).fetchall()]
    out = {"query": name, "placed_on_device": placed, "rows": len(want)}
    checks = {"placed_on_device": bool(placed)}
    for run in runs:
        rows, secs, d = _run_counted(client, sql)
        out[f"{run}_s"] = secs
        out[run] = d
        checks[f"{run}_rows_equal_sqlite"] = _rows_match(rows, want)
        checks[f"{run}_dispatches>0"] = d["dispatches"] > 0
        checks[f"{run}_host_dispatches==0"] = d["host_dispatches"] == 0
    return out, checks, rows


def _finish(phase: str, t0: float, out: dict, checks: dict) -> None:
    """Print the line first, so the numbers are seen, then fail on a
    check that did not hold."""
    _line(phase, t0, **out, checks=checks)
    _require(checks, f"{phase} {out['query']}")


def phase_queries(env: _Loaded) -> None:
    """Q1, Q3, Q6 over the wire, each cold then warm; the warm run
    compiles nothing."""
    from tinysql_tpu.bench import tpch
    for name in QUERY_NAMES:
        t0 = time.time()
        out, checks, _rows = _query_runs(
            env.client, env.mirror, name, tpch.QUERIES[name],
            ("cold", "warm"))
        checks["warm_compiles==0"] = out["warm"]["progcache_misses"] == 0
        _finish("queries", t0, out, checks)


def phase_write_then_read(env: _Loaded) -> None:
    """An acknowledged write is read back.  Multi-row INSERTs fill a
    scratch table; a grouped aggregate runs on the device twice — the
    first read after a write scans the row store and hydrates the
    columnar replica, the second reads the replica uploaded to the
    device; an UPDATE commits, which drops the replica
    (columnar/store.py bump_table_version); the same two reads again must
    see the update, re-upload the replica, and compile nothing new."""
    t0 = time.time()
    ddl = "create table smoke_w (id bigint primary key, g bigint, v double)"
    c, db = env.client, env.mirror
    c.query(ddl)
    db.execute(ddl.replace("bigint", "integer").replace("double", "real"))
    for lo in range(0, SCRATCH_ROWS, SCRATCH_BATCH):
        rows = [(i + 1, i % 13, round(i * 0.25, 2))
                for i in range(lo, lo + SCRATCH_BATCH)]
        values = ", ".join(f"({i}, {g}, {v})" for i, g, v in rows)
        acked = c.query(f"insert into smoke_w values {values}")
        if acked != len(rows):
            raise SmokeFailure(f"insert acknowledged {acked} rows of "
                               f"{len(rows)}")
        db.executemany("insert into smoke_w values (?, ?, ?)", rows)
    _line("write-then-read", t0, inserted=SCRATCH_ROWS)
    runs = ("scan", "replica")

    t0 = time.time()
    out, checks, _rows = _query_runs(c, db, "agg-after-insert", SCRATCH_AGG,
                                     runs)
    _finish("write-then-read", t0, out, checks)

    t0 = time.time()
    acked = c.query(SCRATCH_UPDATE)
    changed = db.execute(SCRATCH_UPDATE).rowcount
    out, checks, _rows = _query_runs(c, db, "agg-after-update", SCRATCH_AGG,
                                     runs)
    out["update_acknowledged_rows"] = acked
    checks["update_acknowledged==sqlite_rowcount"] = acked == changed
    checks["replica_uploaded_again"] = out["replica"]["h2d_bytes"] > 0
    for run in runs:
        checks[f"{run}_compiles==0"] = out[run]["progcache_misses"] == 0
    _finish("write-then-read", t0, out, checks)


def phase_mesh(env: _Loaded, watch: _LogWatch) -> None:
    """``--mesh``: Q1, Q3 and Q6 under ``tidb_mesh_parallel = 1`` (off by
    default) beside the same three on one device — rows equal to each
    other and to sqlite, no numpy twin, a warm statement one dispatch
    over the mesh that lays out no input anew — and what each device
    holds afterwards: the replica's lanes are spread over the mesh, so
    the fullest device holds under half of it all."""
    import jax
    from tinysql_tpu.bench import tpch
    c = env.client
    for name in QUERY_NAMES:
        t0 = time.time()
        sql = tpch.QUERIES[name]
        c.query("set @@tidb_mesh_parallel = 0")
        _rows, one_cold_s, one_cold = _run_counted(c, sql)
        one_rows, one_warm_s, one_warm = _run_counted(c, sql)
        c.query("set @@tidb_mesh_parallel = 1")
        out, checks, mesh_rows = _query_runs(
            c, env.mirror, name, sql, ("mesh_cold", "mesh_warm"))
        out.update(one_device_cold_s=one_cold_s, one_device_cold=one_cold,
                   one_device_warm_s=one_warm_s, one_device_warm=one_warm)
        checks["mesh_rows_equal_one_device"] = _rows_match(mesh_rows,
                                                           one_rows)
        checks["mesh_warm_compiles==0"] = \
            out["mesh_warm"]["progcache_misses"] == 0
        checks["mesh_warm_dispatches==1"] = \
            out["mesh_warm"]["dispatches"] == 1
        checks["mesh_warm_reshard_bytes==0"] = \
            out["mesh_warm"]["reshard_bytes"] == 0
        _finish("mesh", t0, out, checks)
    per_device = []
    for d in jax.devices():
        stats = d.memory_stats()
        per_device.append(None if stats is None
                          else int(stats["bytes_in_use"]))
    held = [b for b in per_device if b is not None]
    checks = {
        "no_warning_on_tinysql_tpu_logger": not watch.failing,
        # the CPU backend reports no memory statistics: only there may
        # this check be skipped
        "fullest_device_under_half_of_all":
            2 * max(held) < sum(held) if held
            else jax.devices()[0].platform == "cpu"}
    _line("mesh", time.time(), per_device_bytes_in_use=per_device,
          warnings=watch.failing, checks=checks)
    _require(checks, "mesh")


def phase_nothing_hid(env: _Loaded, watch: _LogWatch, device: dict) -> None:
    t0 = time.time()
    import jax
    from tinysql_tpu.ops import degrade
    deg = degrade.snapshot()
    need = int(sum(env.data["lineitem"][c].nbytes
                   for c in LINEITEM_NUMERIC_READ))
    stats = jax.devices()[0].memory_stats()
    peak = None if stats is None else int(stats["peak_bytes_in_use"])
    # the memory rules judge the resident set from the last program
    # load on:
    # first answers at this scale grow it by gigabytes, and none of
    # that is a leak or a statement's to answer for
    from tinysql_tpu.obs import inspect as oinspect, tsring
    tsring.RING.sample_once()
    ctx = oinspect.InspectionContext(tsring.RING)
    whole = ctx.series("tinysql_mem_rss_bytes")
    settled = ctx.settled_series("tinysql_mem_rss_bytes")
    memory_findings = [f.to_dict() for f in oinspect.run()
                       if f.rule in ("heap-growth", "mem-untracked")]
    checks = {
        "no_memory_finding_after_first_answers": not memory_findings,
        "device_loss_total==0": deg["device_loss_total"] == 0,
        "degraded_statements_total==0":
            deg["degraded_statements_total"] == 0,
        "cpu_pinned==0": deg["cpu_pinned"] == 0,
        "no_warning_on_tinysql_tpu_logger": not watch.failing,
        # the CPU backend reports no memory statistics: only there may
        # this check be skipped
        "peak_bytes_in_use>=lineitem_columns_read":
            (device["platform"] == "cpu") if peak is None
            else peak >= need,
    }
    _line("nothing-hid-the-device", t0, degrade=deg,
          warnings=watch.failing, peak_bytes_in_use=peak,
          lineitem_numeric_bytes_read=need,
          rss_samples=len(whole),
          rss_samples_since_last_load=len(settled),
          rss_growth_mib=round((whole[-1][1] - whole[0][1]) / 2 ** 20, 1),
          rss_growth_since_last_load_mib=round(
              (settled[-1][1] - settled[0][1]) / 2 ** 20, 1),
          memory_findings=memory_findings, checks=checks)
    _require(checks, "nothing-hid-the-device")


# ---- entry ----------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (default 1)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--expect-platform", default="tpu",
                    help="platform jax.devices() must report; 'cpu' is "
                         "for the rehearsal and the tier-1 test only")
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: the device and load phases, then "
                         "only Q1, Q3 and Q6 under tidb_mesh_parallel = 1 "
                         "beside the same three on one device")
    args = ap.parse_args(argv)

    watch = _LogWatch()
    logger = logging.getLogger("tinysql_tpu")
    logger.addHandler(watch)
    env = _Loaded()
    device = None
    ok = False
    try:
        device = phase_device()
        expect_device(device, args.expect_platform, 4 if args.mesh else 1)
        phase_load(args.sf, args.seed, env)
        if args.mesh:
            phase_mesh(env, watch)
        else:
            phase_queries(env)
            phase_write_then_read(env)
            phase_nothing_hid(env, watch, device)
        ok = True
    except Exception:  # the boundary: report, then exit non-zero
        traceback.print_exc()
    finally:
        logger.removeHandler(watch)
        env.close()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

"""Deployment ``columnar_inprocess``: one server in the harness's own
process (so that its counters can be read) over a volatile store, the
tables bulk-loaded into the columnar replica before the server starts.
The phases are ``chip_smoke.py``'s.  A configuration names its deployment;
another store, loader or front end is another file here.
"""
from __future__ import annotations

import os

#: WARNING-or-worse records of this logger fail the run: there the fused
#: pipeline's bail-out and the degraded re-run speak
LOGGER = "tinysql_tpu"
#: the sources (``sources/``) that ``checks`` reads, whatever the metrics
SOURCES = ("kernels",)


def prepare(config: dict, rehearsal: bool) -> None:
    """Before jax is first touched.  ``rehearsal`` (``--expect-platform
    cpu`` only): the chip's branches on the CPU at a tiny scale, see the
    configuration's ``rehearsal`` block."""
    if rehearsal:
        os.environ.update(config["rehearsal"]["env"])
        from tinysql_tpu.session import session as session_mod
        session_mod.DEFAULT_SYSVARS.update(
            config["rehearsal"]["default_sysvars"])
    from tinysql_tpu.ops import kernels
    kernels.jax()  # the engine's one-time jax configuration (x64, cache)


class Deployment:
    def __init__(self, storage, server, rows: dict):
        self.storage, self.server, self.rows = storage, server, rows
        self.port = server.port

    def close(self) -> None:
        self.server.close()
        self.storage.close()


def start(config: dict, dataset_module, dataset) -> Deployment:
    from tinysql_tpu.columnar.store import bulk_load
    from tinysql_tpu.kv import new_mock_storage
    from tinysql_tpu.server.server import Server
    from tinysql_tpu.session.session import Session
    storage = new_mock_storage()
    boot = Session(storage)
    boot.execute(f"create database if not exists {dataset_module.DATABASE}")
    boot.execute(f"use {dataset_module.DATABASE}")
    rows = {}
    for table, ddl in dataset_module.SCHEMAS.items():
        boot.execute(ddl)
        info = boot.infoschema().table_by_name(dataset_module.DATABASE,
                                               table)
        rows[table] = bulk_load(boot.storage, info, dataset.tables[table])
    for name, value in config["sysvars"].items():
        boot.execute(f"set global {name} = {value}")
    server = Server(storage, port=0)
    server.start()
    return Deployment(storage, server, rows)


def checks(deltas: dict, warnings: list) -> tuple:
    """The smoke's "nothing hid the device", in every run: a quiet CPU
    answer is never timed.  Returns ({check: passed}, what to print)."""
    from tinysql_tpu.ops import degrade
    deg = degrade.snapshot()
    kernels = deltas["kernels"]
    return {
        "dispatches>0": kernels.get("dispatches", 0) > 0,
        "host_dispatches==0": kernels.get("host_dispatches") == 0,
        "device_loss_total==0": deg["device_loss_total"] == 0,
        "degraded_statements_total==0":
            deg["degraded_statements_total"] == 0,
        "cpu_pinned==0": deg["cpu_pinned"] == 0,
        "no_warning_on_the_programs_logger": not warnings,
    }, {"degrade": deg}

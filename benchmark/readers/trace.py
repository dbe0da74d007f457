"""Reader ``trace``: what the traced part of the window shows of the
device (``tracered.py`` makes the reduction).  Nothing in a run that was
not traced or whose trace holds no device operation: never a zero.

- ``idle_share``: 100 * (1 - busy / traced seconds);
- ``busy_ms_per_query``: device-busy ms over the statements answered in
  the traced part (a statement partly inside counts by its share);
- ``scan_roofline``: 100 * (least seconds the chip could take to read the
  bytes those statements must read, by the schema's types and the
  generator's row counts, at the table of peaks' memory bandwidth) / busy
  seconds.  Memory-bound by construction: the three queries do a few
  operations a byte.
"""


def _inside(run):
    """(statement index, share of the statement inside the traced part)."""
    t0, t1 = run.trace_start, run.trace_stop
    out = []
    for r in run.answered:
        overlap = min(r[3], t1) - max(r[2], t0)
        if overlap > 0:
            out.append((r[1], overlap / max(r[3] - r[2], 1e-9)))
    return out


def read(run, what):
    red = run.trace
    if red is None or red.busy_s <= 0:
        return None
    if what == "idle_share":
        return 100.0 * red.idle_share
    inside = _inside(run)
    if not inside:
        return None
    if what == "busy_ms_per_query":
        return red.busy_s * 1e3 / sum(share for _i, share in inside)
    if what == "scan_roofline":
        rows = {t: len(next(iter(cols.values())))
                for t, cols in run.dataset.tables.items()}
        need = sum(share * run.dataset_module.scan_bytes(
            run.statements[i].reads, rows) for i, share in inside)
        return 100.0 * (need / run.peaks["hbm_bytes_per_s"]) / red.busy_s
    raise ValueError(f"unknown trace quantity {what!r}")

"""Reader ``mesh_scan_roofline``: the ``trace`` reader's ``scan_roofline``
for a cell on several chips.  The rows are split over the cell's chips and
each chip reads its own share at its own memory bandwidth, so the least
time is the bytes over ``chips`` x one chip's peak; the busy seconds are
``tracered``'s, averaged over the chips.  (``scan_roofline`` divides by one
chip's peak, which over four chips would overstate the share four times.)
Nothing in a run that was not traced."""


def inside(run) -> list:
    """(statement index, share of the statement inside the traced part),
    as ``readers/trace.py`` counts them."""
    t0, t1 = run.trace_start, run.trace_stop
    out = []
    for r in run.answered:
        overlap = min(r[3], t1) - max(r[2], t0)
        if overlap > 0:
            out.append((r[1], overlap / max(r[3] - r[2], 1e-9)))
    return out


def scan_seconds(run, shares: list) -> float:
    """Least seconds the cell's chips take to read what the statements
    of ``shares`` must read."""
    rows = {t: len(next(iter(cols.values())))
            for t, cols in run.dataset.tables.items()}
    need = sum(share * run.dataset_module.scan_bytes(
        run.statements[i].reads, rows) for i, share in shares)
    return need / (run.cell.entry["chips"] * run.peaks["hbm_bytes_per_s"])


def read(run):
    red = run.trace
    if red is None or red.busy_s <= 0:
        return None
    shares = inside(run)
    if not shares:
        return None
    return 100.0 * scan_seconds(run, shares) / red.busy_s

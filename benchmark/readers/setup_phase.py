"""Reader ``setup_phase``: seconds of one of the harness's own set-up
spans (``device``, ``generate``, ``load``, ``warm``) or, as ``total``,
from the start of the process to the opening of the window."""


def read(run, phase):
    return run.phase_seconds.get(phase)

"""OB408 fixture: every way of writing the span totals from outside
obs/trace.py, beside reads and look-alikes that must stay silent."""
from tinysql_tpu.obs import trace
from tinysql_tpu.obs import trace as obs_trace
from tinysql_tpu.obs.trace import _GC, _TOTALS, _count, totals


def launder(seconds):
    trace._TOTALS["plan"] = [1, seconds, seconds, seconds]    # OB408
    obs_trace._TOTALS["plan"][1] += seconds                   # OB408
    _TOTALS.setdefault("plan", [0, 0.0, 0.0, 0.0])            # OB408
    trace._TOTALS.clear()                                     # OB408
    _GC[1] += seconds                                         # OB408
    trace._count("plan", seconds, seconds)                    # OB408
    _count("plan", seconds, seconds)                          # OB408


def fine():
    rows = totals()                       # a read through the door
    copy = dict(trace._TOTALS)            # a read
    n = obs_trace._GC[0]                  # a read
    return rows, copy, n


class Elsewhere:
    _TOTALS = {}                          # an unrelated table

    def _count(self, name):               # an unrelated method
        self._TOTALS[name] = 1


Elsewhere()._count("x")

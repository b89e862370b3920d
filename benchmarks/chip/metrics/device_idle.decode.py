"""Share of the traced serve() calls in which no operation ran on the
device, in % (profiler trace)."""
from chipbench import xtrace


def read(run):
    if run.trace is None:
        return None
    idle = xtrace.idle_share(run.trace, run.busy_windows())
    return None if idle is None else 100.0 * idle

"""Share of the device-idle time in the traced serve() calls that no wall
span of the program covers, other than ``serve``, in %; the idle seconds by
span are logged (device trace, with the program's spans on the profiler's
clock)."""
from chipbench import spans


def read(run):
    return spans.idle_unattributed(run)

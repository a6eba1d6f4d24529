"""Share of the first chip's idle seconds (profiled seconds) that pass
while the thread that drives the device is in no program span finer than
``executor_run`` / ``engine_tick``, each gap shared out by overlap: the
idle time the program cannot put a name to."""

from benchmark.harness import program_spans as ps


def read(ev):
    return ps.idle_unattributed_pct(ev)

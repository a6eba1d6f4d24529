"""Milliseconds the T = 1 step's ``Executor.run`` spent gathering its
arguments: the ``executor_marshal`` phases inside each
``decode_paged_step``, summed a step; the median over the window's
steps. (The prefill windows' marshal is not in it: they are other
programs, a few a second.)"""

from benchmark.harness import program_spans as ps
from benchmark.harness.stats import median


def read(ev):
    spans = ps.in_window(ev)
    marshals = ps.named(spans, "executor_marshal")
    xs = [sum(ps.ms(m) for m in ps.inside(step, marshals))
          for step in ps.named(spans, "decode_paged_step")]
    xs = [x for x in xs if x > 0]
    return median(xs) if xs else None

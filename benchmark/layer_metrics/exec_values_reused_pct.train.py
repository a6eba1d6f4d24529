"""Of the values ``executor_marshal`` gathered in the window, the share
handed to the executable on the identity check alone (``reused`` over
``values``): near 100 where a step's outputs are the next step's inputs
and only the feeds are new, 0 where every value is looked up again."""

from benchmark.harness import marshal_notes


def read(ev):
    return marshal_notes.reused_pct(ev)

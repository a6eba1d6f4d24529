"""Of the values ``executor_marshal`` gathered in the window, over every
program of the engine (the T = 1 step, the prefill windows, the block
copy), the share handed to the executable on the identity check alone
(``reused`` over ``values``). The programs share the pools: the one that
runs after another looks up the pools that one wrote and reuses the
weights."""

from benchmark.harness import marshal_notes


def read(ev):
    return marshal_notes.reused_pct(ev)

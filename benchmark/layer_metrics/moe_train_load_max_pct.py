"""The fullest held expert's assignments in a train step over the even
share ``T * k / E`` (``expert_load_max`` of the ``train_step`` spans,
median over the window): 100 where routing is even, more under
imbalance, which the grouped products must take without dropping."""

from benchmark.harness import program_spans as ps
from benchmark.kernels import moe_train


def read(ev):
    fullest = ps.median_arg(ps.in_window(ev), "train_step",
                            lambda a: (a or {}).get("expert_load_max"))
    if fullest is None:
        return None
    return 100.0 * fullest / moe_train.even_share(ev.config, ev.traffic)

"""The whole train step's share of the chip's peak: the model's FLOPs a
step (``kernels/moe_train.py::model_flops``: attention causal and once,
recomputation not counted) over ``step_device_ms.train`` times the peak
bf16 rate."""

from benchmark.harness import cells
from benchmark.kernels import moe_train


def read(ev):
    busy_ms = cells.load_module("layer_metrics", "step_device_ms.train",
                                ev.ctx.cell.bench_dir).read(ev)
    if not busy_ms:
        return None
    flops = moe_train.model_flops(ev.config, ev.traffic)
    return 100.0 * flops / (1e-3 * busy_ms * ev.peaks["bf16_flops"])

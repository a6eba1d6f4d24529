"""Median ``decode_paged_step`` span: one T = 1 step of all slots, host
clock around dispatch and the fetch of the next tokens."""

from benchmark.harness.stats import median


def read(ev):
    xs = [1e3 * (s["end"] - s["start"]) for s in ev.spans
          if s["name"] == "decode_paged_step"]
    return median(xs) if xs else None

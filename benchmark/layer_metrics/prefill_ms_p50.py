"""Median ``decode_paged_window`` span: one prompt window through the
dense prefill program into the paged pool."""

from benchmark.harness.stats import median


def read(ev):
    xs = [1e3 * (s["end"] - s["start"]) for s in ev.spans
          if s["name"] == "decode_paged_window"]
    return median(xs) if xs else None

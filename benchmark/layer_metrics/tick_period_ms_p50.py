"""Median time between the starts of consecutive ``decode_tick`` spans
(``DecodeEngine._tick``) inside the window."""

from benchmark.harness.stats import median


def read(ev):
    starts = sorted(s["start"] for s in ev.spans if s["name"] == "decode_tick")
    gaps = [1e3 * (b - a) for a, b in zip(starts, starts[1:])]
    return median(gaps) if gaps else None

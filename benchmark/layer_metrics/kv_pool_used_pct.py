"""Median over the window's ticks of KV blocks handed out over the
pool's blocks (``engine_tick``'s ``blocks_in_use`` / ``blocks_total``)."""

from benchmark.harness import program_spans as ps


def read(ev):
    return ps.median_arg(
        ps.in_window(ev), "engine_tick",
        lambda a: 100.0 * a["blocks_in_use"] / a["blocks_total"]
        if a.get("blocks_total") else None)

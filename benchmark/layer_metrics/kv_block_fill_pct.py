"""Median over the window's ticks of the tokens the active streams hold
over the room of the blocks handed out (``engine_tick``'s ``live_tokens``
/ (``blocks_in_use`` x block size)): blocks reserved against used."""

from benchmark.harness import program_spans as ps


def read(ev):
    block = ev.config.get("serve", {}).get("block_size")
    if not block:
        return None
    return ps.median_arg(
        ps.in_window(ev), "engine_tick",
        lambda a: 100.0 * a["live_tokens"] / (a["blocks_in_use"] * block)
        if a.get("blocks_in_use") else None)

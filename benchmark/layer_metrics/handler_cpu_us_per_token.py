"""Microseconds of CPU the gateway's SSE handler threads held a token
they streamed: ``gateway_handler_cpu_us`` (each handler's
``time.thread_time`` over its stream, bumped with the events) over
``gateway_stream_tokens``, both over the window. A program that does not
count its handlers' CPU leaves nothing to read."""


def read(ev):
    cpu_us = ev.counters.get("gateway_handler_cpu_us")
    tokens = ev.counters.get("gateway_stream_tokens")
    if cpu_us is None or not tokens:
        return None
    return cpu_us / tokens

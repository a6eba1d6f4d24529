"""Tokens emitted per decode step over the window: the ``decode_tokens``
counter's rise over the ``decode_steps`` counter's."""


def read(ev):
    steps = ev.counters.get("decode_steps", 0)
    return ev.counters.get("decode_tokens", 0) / steps if steps else None

"""Socket sends the gateway's SSE handlers made a token they streamed:
``gateway_stream_sends`` (a send of token events, or of a stream's
terminal event with the body's last chunk) over ``gateway_stream_tokens``,
both bumped together, every few tokens of a stream and when it ends. A
chunk written as size line, payload and trailer is three sends a token; a
chunk a send reads a little over 1 (a request's done event). A program
that does not count its sends leaves nothing to read."""


def read(ev):
    sends = ev.counters.get("gateway_stream_sends")
    tokens = ev.counters.get("gateway_stream_tokens")
    if sends is None or not tokens:
        return None
    return sends / tokens

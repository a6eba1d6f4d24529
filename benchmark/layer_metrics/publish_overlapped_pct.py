"""Share of the window's served tokens that the engine handed to their
streams' readers while a device call of its loop thread was in flight
(``decode_tokens_published_overlapped``) and not with the chip waiting
(``decode_tokens_published_exposed``): how often the handlers' wake-ups,
SSE writes and the clients' reads run under the fetch."""


def read(ev):
    under = ev.counters.get("decode_tokens_published_overlapped")
    bare = ev.counters.get("decode_tokens_published_exposed")
    if under is None and bare is None:
        return None
    under, bare = under or 0, bare or 0
    if not under + bare:
        return None
    return 100.0 * under / (under + bare)

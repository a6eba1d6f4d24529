"""Share of the window's T = 1 step tokens whose pick was made on the
device, by the step program's own argmax and fetched as an id
(``decode_picks_on_device``), and not on the host from a logits row read
back for a stream that samples (``decode_picks_on_host``): what a step's
fetch has to bring to the host. A request's first token, picked from its
prefill window's one row, is in neither."""


def read(ev):
    device = ev.counters.get("decode_picks_on_device")
    host = ev.counters.get("decode_picks_on_host")
    if device is None and host is None:
        return None
    device, host = device or 0, host or 0
    if not device + host:
        return None
    return 100.0 * device / (device + host)

"""Mean, over the requests that ended in the window, of the steps the
engine's loop made while the request waited in the queue: its
``decode_request`` record's ``dequeue_tick`` less ``submit_tick`` (the
engine's step counter read in ``submit`` on the handler's thread, and
where the loop dequeued it). 0: the POST reached ``submit`` before that
tick's admission; 1 or more: the loop had passed it and the request
waited a device call out. What ``queue_wait_ms_p50`` is made of."""

from benchmark.harness import program_spans as ps


def read(ev):
    xs = [r["args"]["dequeue_tick"] - r["args"]["submit_tick"]
          for r in ps.instants(ps.in_window(ev), "decode_request")
          if r["args"].get("submit_tick") is not None
          and r["args"].get("dequeue_tick") is not None]
    if not xs:
        return None
    ev.ctx.note("admit_ticks_waited", requests=len(xs),
                at_once_pct=100.0 * sum(1 for x in xs if x == 0) / len(xs))
    return sum(xs) / len(xs)

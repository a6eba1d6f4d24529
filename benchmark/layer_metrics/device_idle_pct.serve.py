"""Share of the profiled seconds in which no operation ran on the chip
(averaged over the chips)."""


def read(ev):
    t = ev.device_times()
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

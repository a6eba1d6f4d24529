#!/usr/bin/env python3
"""The load generator with a clock of its own, as the child of a test:
``loadgen_shifted.py <offset seconds> <drift, seconds a second>``."""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", "harness"))
import loadgen  # noqa: E402

OFFSET, DRIFT = float(sys.argv[1]), float(sys.argv[2])
BORN = time.perf_counter()


def clock():
    now = time.perf_counter()
    return now + OFFSET + DRIFT * (now - BORN)


loadgen.main(clock)

"""Test config: run on the jax CPU backend with 8 virtual devices so
multi-chip SPMD paths are exercised without TPU hardware (the reference's
philosophy of simulating multi-node on localhost — test_dist_base.py)."""

import os

# must be set before jax backends initialize
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-process / e2e-training tests (deselect with -m 'not slow' "
        "for a fast inner loop; the full suite always runs them). Heavy "
        "modules mark themselves at the source via pytestmark.",
    )


def run_probe_subprocess(script, args=("--fast",), retry_prefix=None,
                         timeout=600):
    """Run a tools/ closed-loop probe in a subprocess and parse its
    REPORT line: returns (completed_process, report_dict_or_None).

    ``retry_prefix`` opts into the decode-probe retry policy shared by
    the probe acceptance tests: when the probe fails and EVERY failure
    string starts with the prefix (a throughput-only miss — the 2-core
    driver box throttles under load, which compresses throughput but
    cannot corrupt outputs/parities/recompile counts), the probe earns
    exactly one retry; correctness misses fail immediately. A tuple of
    prefixes (str.startswith semantics) covers probes with several
    load-sensitive bars (throughput, TTFT gain, inter-token p99).
    """
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def _run():
        p = subprocess.run(
            [sys.executable, os.path.join(repo, "tools", script), *args],
            cwd=repo, capture_output=True, text=True, timeout=timeout,
            env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=""),
        )
        report = None
        for ln in p.stdout.splitlines():
            if ln.startswith("REPORT "):
                report = json.loads(ln[len("REPORT "):])
        return p, report

    p, report = _run()
    if (retry_prefix and p.returncode != 0 and report is not None
            and report.get("failures")
            and all(f.startswith(retry_prefix)
                    for f in report["failures"])):
        p, report = _run()
    return p, report

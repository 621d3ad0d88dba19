"""Launch the multi-device suite (tests/md) in a subprocess with 8 host
devices.

The harness requires the main pytest process to see exactly ONE device
(XLA_FLAGS is reserved for the dry-run), so the real multi-device validation
— primitive adjoints under shard_map, distributed-vs-sequential layer
equivalence — runs in a child interpreter with
``--xla_force_host_platform_device_count=8``.
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# A hung or runaway subprocess fails this test instead of stalling the
# whole tier-1 run; the suite takes about 520 s of it on 8 CPU cores.
TIMEOUT_S = 1200


@pytest.mark.md
@pytest.mark.slow
@pytest.mark.timeout(TIMEOUT_S)
def test_multidevice_suite():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["REPRO_MD_SUITE"] = "1"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        # -m "not slow": the heaviest files (ring attention, LeNet, the
        # adjoint fuzzer, MoE, ring collective-matmuls) ride the CI
        # multidevice job's slow leg (ci.yml); the goldens keep the CP and
        # EP paths here.
        [sys.executable, "-m", "pytest", os.path.join(ROOT, "tests", "md"),
         "-q", "--no-header", "-x", "-m", "not slow"],
        env=env,
        capture_output=True,
        text=True,
        timeout=TIMEOUT_S,
    )
    if proc.returncode != 0:
        sys.stdout.write(proc.stdout[-8000:])
        sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, "multi-device suite failed (see output above)"

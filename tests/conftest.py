"""Test harness configuration.

Tests run on the CPU backend with 8 virtual devices and never take an
accelerator, whatever the machine defaults to; the environment must be set
before jax initializes its backends (and is inherited by every subprocess
a test starts).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

# Tier-1 runs with the runtime lock-order detector armed (must be set
# before the first torchft_tpu import, which creates the instrumented
# locks).  Export TORCHFT_LOCKCHECK=0 to opt out locally.
os.environ.setdefault("TORCHFT_LOCKCHECK", "1")

# ...and with live topology-plan verification armed (ISSUE 19): every
# reduction plan build, serving tree_commit, and stripe resolution the
# suite exercises is validated against the tft-plan invariant catalog.
# Observe-only (a rejection is metrics + flight record + ERROR log, never
# a raise); tests/test_plan_verify.py gates on zero rejections.  Export
# TORCHFT_PLAN_VERIFY=0 to opt out locally.
os.environ.setdefault("TORCHFT_PLAN_VERIFY", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

"""Test harness: force an 8-device virtual CPU mesh BEFORE jax initializes.

This is the fake-backend story the reference never had (SURVEY.md §4):
pjit/GSPMD collectives run deterministically on N virtual CPU devices, so
multi-chip sharding is exercised in CI without a pod.
"""

import os

# Tests run on the CPU: set DEEPFM_TEST_TPU=1 to run them on the chip
# instead.
if not os.environ.get("DEEPFM_TEST_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    # 8 virtual devices time-slice few (often 1) CI cores: raise XLA:CPU's
    # 20s-warn/40s-KILL collective rendezvous watchdogs, which heavyweight
    # compiles or steps can trip on an oversubscribed host
    if "xla_cpu_collective_call_terminate_timeout_seconds" not in flags:
        flags += (
            " --xla_cpu_collective_call_warn_stuck_timeout_seconds=120"
            " --xla_cpu_collective_call_terminate_timeout_seconds=900"
        )
    os.environ["XLA_FLAGS"] = flags
# The persistent compile cache stays OFF under test (here and in every
# subprocess a test starts): entry points place it inside the checkout
# (core/platform.configure_runtime), and a test run must not grow the tree.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
# pytest plugins may import jax before this conftest, baking the ambient
# environment in: override the live config too
try:
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    if not os.environ.get("DEEPFM_TEST_TPU"):
        jax.config.update("jax_platforms", "cpu")
except ImportError:  # pure-data tests run without jax installed
    pass

import pathlib

import pytest

REFERENCE_VAL_TFRECORDS = pathlib.Path("/root/reference/data/val.tfrecords")


@pytest.fixture(scope="session")
def reference_val_tfrecords():
    if not REFERENCE_VAL_TFRECORDS.exists():
        pytest.skip("reference val.tfrecords not available")
    return REFERENCE_VAL_TFRECORDS

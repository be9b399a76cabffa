"""Test configuration: force an 8-device CPU mesh.

Must run before any jax backend is initialized (backend init is lazy, so
this works as long as no fixture touched jax.devices() earlier).  Eight
virtual CPU devices let multi-chip sharding tests run without TPU hardware
(SURVEY.md §4).
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the full suite compiles hundreds of programs;
# a warm cache cuts suite latency from ~25 min to well under 10.  Keyed by
# jax/XLA version internally, so stale entries are never reused.
from deepfake_detection_tpu.utils.compile_cache import (  # noqa: E402
    setup_compile_cache)

setup_compile_cache(min_compile_secs=0.5)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices).reshape(4, 2), ("data", "model"))

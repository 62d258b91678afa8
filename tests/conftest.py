"""Test harness config.

Mirrors the reference's test strategy of running distributed logic without
real accelerators (SURVEY.md §4): force an 8-device virtual CPU platform so
mesh/sharding/collective tests exercise real XLA partitioning.

Must run before jax initializes its backends, hence env vars set at import.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax

# An interpreter start-up hook may have imported jax before this conftest
# ran, so the env var above can be too late — force it on the live config
# too (must happen before any backend is touched by tests).
jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: repeat suite runs skip XLA compiles (the
# dominant cost of these CPU tests). Keyed by backend+flags, safe across
# the virtual 8-device mesh.
from paddle_tpu.core.compile_cache import enable_compile_cache

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
import numpy as np
import pytest

# Numeric tests compare against NumPy in fp32; force exact fp32 contractions
# (the TPU bench path keeps the backend default / bf16 AMP).
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(scope="session", autouse=True)
def _flight_dumps_out_of_the_tree(tmp_path_factory):
    """The obs ring is on from import, so every death path a chaos test
    walks writes a flight record: send them to a temporary directory, not
    to artifacts/ in the checkout."""
    from paddle_tpu.core.flags import GLOBAL_FLAGS

    GLOBAL_FLAGS.set("obs_dir", str(tmp_path_factory.mktemp("flightrec")))
    yield


@pytest.fixture(autouse=True)
def _seed_everything():
    import paddle_tpu

    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield
    # Drop dead Layers/optimizers promptly: the capture state registry
    # (jit/capture.py) reflects live Parameters, and reference cycles in
    # Layer graphs otherwise survive into later tests.
    import gc

    gc.collect()

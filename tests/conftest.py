"""Test config: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference's strategy of testing device-independent plumbing on
fake backends (SURVEY.md §4: fake_cpu_device.h, ProcessGroupGloo): all
sharding/parallelism tests run on 8 virtual CPU devices so no TPU pod is
needed. The platform is pinned with jax.config.update, so the suite
stays on the CPU whatever JAX_PLATFORMS says and never takes a chip.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# the suite asks for the CPU, in the way the entry scripts look for
# (bench.cpu_requested) as well as in JAX's own
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache: the suite builds hundreds of
# engines whose unified programs lower to identical HLO (same tiny-GPT
# geometry, same slot/page shapes), and on a 1-core box those duplicate
# compiles dominate tier-1 wall-clock. The disk cache dedups them both
# within one run and across runs (same executable bytes — numerics and
# the in-memory jit trace counts the retrace probes assert on are
# untouched). Opt out with PADDLE_TPU_TEST_NO_COMPILE_CACHE=1.
if not os.environ.get("PADDLE_TPU_TEST_NO_COMPILE_CACHE"):
    from paddle_tpu.utils.compile_cache import use_compile_cache
    use_compile_cache()
    # Only executables that took >= 1s to compile are persisted:
    # that captures every serving unified-step program (the whales)
    # while skipping the long tail of tiny layer/RNN executables.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_parallel_state():
    """Tests that init fleet/meshes must not leak the thread-local mesh
    into later tests (models built under a stale mesh mix device sets)."""
    yield
    from paddle_tpu.distributed import fleet
    fleet.shutdown()


@pytest.fixture
def only_the_unified_step():
    """A check that a ServingEngine holds no step program but
    `_unified_fn`: every other compiled-program attribute is one of its
    helpers (embed epilogue, COW copy, host-tier swaps), and the step
    never retraced."""
    def check(eng):
        assert {k for k in vars(eng) if k.endswith(("_fn", "_fns"))} == {
            "_unified_fn", "_embed_fn", "_copy_page_fn", "_swap_out_fn",
            "_swap_in_fn"}
        assert eng._unified_fn._cache_size() == 1
    return check

"""Test harness configuration.

Multi-chip behaviour is tested on a virtual 8-device CPU mesh (the
driver's dryrun does the same), mirroring how the reference tests
multi-node behaviour in a single process with madsim (SURVEY.md §4.4).
Must run before jax initializes.
"""

import os

# tests run on the CPU whatever the machine holds: a chip belongs to
# one process, and the suite starts many (xdist workers, cluster
# children — they inherit this environment)
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# The persistent compile cache stays OFF for the suite, children
# included (jax reads this variable at import), wherever
# JAX_COMPILATION_CACHE_DIR points and whatever the package sets: six
# workers and their children would share one directory, and a test must
# compile what it tests.  (The corruption of cache-served donated
# programs seen on jax 0.4.37 did not reproduce on 0.9.0 with every
# program of test_cold_start/test_chaos served from one directory —
# CHANGES.md PR 22 — so this is hygiene, not a ban.)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", True)


@pytest.fixture
def accel_branch(monkeypatch):
    """A setter for the trace-time branch behind
    ``compact.accel_tuned()``: ``accel_branch(True)`` is the chip's
    (``lax.top_k``, sort-based pre-aggregation), ``False`` the CPU's
    (``jnp.nonzero``, per-row probes).  ``hash_agg`` imports the name,
    so it is patched there too."""
    from risingwave_tpu.common import compact
    from risingwave_tpu.stream import hash_agg

    def choose(chip: bool) -> None:
        monkeypatch.setattr(compact, "accel_tuned", lambda: chip)
        monkeypatch.setattr(hash_agg, "accel_tuned", lambda: chip)

    return choose


@pytest.fixture(params=[False, True], ids=["cpu_branch", "chip_branch"])
def accel_tuned(request, accel_branch):
    """Both branches of ``accel_branch``, same expected rows."""
    accel_branch(request.param)
    return request.param


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running stress tests excluded from the tier-1 run",
    )

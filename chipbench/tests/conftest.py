"""chipbench's own tests run on the CPU, on four virtual devices, and never claim
a chip.  They rehearse control flow and check the yardstick's arithmetic; they say
nothing about speed.

    python -m pytest chipbench/tests -q -p no:cacheprovider
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="session")
def _no_persistent_cache():
    """Keep rehearsals' executables out of the checkout's compile cache."""
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    yield


def small(cell_name: str) -> dict:
    """Rehearsal sizes: the harness-internal hook that shrinks a configuration."""
    if "logreg" in cell_name:
        return {
            "data": {"rows_per_chip": 4096, "cols": 64},
            "params": {"maxIter": 20},
            "config": {"expected_iters": 20},
            "check": {"score_rows": 512},
            "limits": {"score_gap": 0.02},
        }
    return {
        "data": {"rows_per_chip": 4096, "cols": 64, "k_true": 16, "ridges": 2, "ridge_share": 0.2, "ridge_scale": 2.0},
        "params": {"k": 16, "maxIter": 5},
        "config": {"expected_iters": 5},
        # after 5 iterations on 16 clusters some centres still move by a fifth of their norm
        "limits": {"fixed_point_gap_p75": 0.5, "fixed_point_gap_p90": 0.5, "fixed_point_gap_p95": 0.5, "fixed_point_gap_worst": 0.5},
    }

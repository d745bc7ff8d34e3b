"""What every subject takes from the program: its mesh rule, its one compile-cache
rule and its counters (read, never written).  Besides subjects/, this is the
only file of chipbench that imports the program."""
from __future__ import annotations

from typing import Dict

from spark_rapids_ml_tpu import profiling
from spark_rapids_ml_tpu.ops.precompile import ensure_compile_cache
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, get_mesh

__all__ = ["DATA_AXIS", "get_mesh", "ensure_compile_cache", "counters"]


def counters() -> Dict[str, int]:
    return profiling.counters()

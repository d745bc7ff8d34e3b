"""The phase clock of one run: wall-clock marks from the process's start to the
end of the check, printed on a line before the result (ISSUE 23, rule 7)."""
from __future__ import annotations

import os
import time
from typing import Dict, List, Tuple

PHASES = (
    "process_start", "main", "jax_imported", "devices", "imports_done", "data_staged",
    "ready", "warm_done", "window_start", "window_end", "check_end",
)


def process_start_epoch() -> float:
    """When the kernel started this process, from /proc/self/stat (field 22,
    clock ticks since boot) and /proc/stat's btime: 10 ms resolution."""
    with open("/proc/self/stat") as f:
        # the command name (field 2) may hold spaces: split after its ")"
        fields = f.read().rsplit(")", 1)[1].split()
    ticks = int(fields[19])  # field 22, counting from field 3 at index 0
    with open("/proc/stat") as f:
        btime = next(int(l.split()[1]) for l in f if l.startswith("btime"))
    return btime + ticks / os.sysconf("SC_CLK_TCK")


class PhaseClock:
    def __init__(self) -> None:
        self.marks: List[Tuple[str, float]] = []

    def mark(self, name: str, t: float | None = None) -> float:
        t = time.time() if t is None else t
        self.marks.append((name, t))
        return t

    def at(self, name: str) -> float:
        return dict(self.marks)[name]

    def span(self, a: str, b: str) -> float:
        return self.at(b) - self.at(a)

    def since_start(self) -> Dict[str, float]:
        """Seconds from the process's start to each mark."""
        t0 = self.marks[0][1]
        return {name: t - t0 for name, t in self.marks}

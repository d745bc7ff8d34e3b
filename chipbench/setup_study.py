"""The set-up study (ISSUE 23, section 1): where set-up time lives and how far it
repeats.  One chip call.  Copies the tree twice, as the driver runs parent and
change in turn; for each cell makes one compiling run in each copy, then warm runs
alternating the copies, each with another seed; prints each phase's median,
minimum and maximum for each copy, the gap since the previous process exited, and
the cache counts.  This process never touches jax: the children hold the chip.

    python3 -m chipbench.setup_study [--cells a,b] [--runs 8] [--seconds 10] [--out file.json]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SKIP = {".git", "build", "chiprun_out", ".jax_cache", "__pycache__", ".pytest_cache"}
STEPS = (
    ("process_start", "main"), ("main", "jax_imported"), ("jax_imported", "devices"),
    ("devices", "imports_done"), ("imports_done", "data_staged"), ("data_staged", "ready"),
    ("ready", "warm_done"), ("warm_done", "window_start"),
    ("devices", "window_start"), ("process_start", "window_start"),
)


def copy_tree(dst: str) -> None:
    shutil.rmtree(dst, ignore_errors=True)
    shutil.copytree(ROOT, dst, ignore=lambda _d, names: [n for n in names if n in SKIP])


def run(copy: str, home: str, cell: str, seed: int, seconds: float, last_exit: float) -> dict:
    """One run in `copy`, with a HOME, TMPDIR and cache of its own as the driver gives."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    for d in ("home", "tmp", "xdg"):
        os.makedirs(os.path.join(home, d), exist_ok=True)
    env.update(HOME=os.path.join(home, "home"), TMPDIR=os.path.join(home, "tmp"),
               XDG_CACHE_HOME=os.path.join(home, "xdg"))
    cmd = [sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", "0"]
    started = time.time()
    p = subprocess.run(cmd, cwd=copy, env=env, capture_output=True, text=True)
    ended = time.time()
    rec = {"copy": os.path.basename(copy), "cell": cell, "seed": seed, "rc": p.returncode,
           "gap_since_exit_s": started - last_exit, "wall_s": ended - started, "ended": ended}
    lines = p.stdout.strip().splitlines()
    for line in lines:
        if line.startswith("PHASES "):
            rec.update(json.loads(line[len("PHASES "):]))
    if p.returncode == 0 and lines:
        rec["result"] = json.loads(lines[-1])
    else:
        rec["stderr_tail"] = p.stderr[-2000:]
    return rec


def table(records: list) -> list:
    out = []
    for cell in sorted({r["cell"] for r in records}):
        for copy in sorted({r["copy"] for r in records}):
            rows = [r for r in records if r["cell"] == cell and r["copy"] == copy and "phases_s" in r]
            first, warm = rows[:1], rows[1:]
            for label, group in (("first", first), ("warm", warm)):
                if not group:
                    continue
                line = {"cell": cell, "copy": copy, "runs": label, "n": len(group)}
                for a, b in STEPS:
                    v = [r["phases_s"][b] - r["phases_s"][a] for r in group]
                    line[f"{a}->{b}"] = [round(statistics.median(v), 3), round(min(v), 3), round(max(v), 3)]
                line["gap_since_exit_s"] = [round(f(r["gap_since_exit_s"] for r in group), 3) for f in (statistics.median, min, max)]
                line["cache_misses"] = [r["cache"]["misses"] for r in group]
                line["cache_hits"] = [r["cache"]["hits"] for r in group]
                line["files_added"] = [len(r["cache_files_added"]) for r in group]
                line["precompile.compile"] = [r["cache"]["precompile"]["precompile.compile"] for r in group]
                out.append(line)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="kmeans_fit,logreg_fit")
    ap.add_argument("--runs", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "setup_study.json"))
    args = ap.parse_args(argv)
    base = os.path.join(ROOT, "build", "study")
    copies = [os.path.join(base, n) for n in ("a", "b")]
    for c in copies:
        copy_tree(c)
    records, last_exit, seed = [], time.time(), 3_000_000_019
    for cell in args.cells.split(","):
        for c in copies:                                   # the compiling run of each copy
            seed += 101
            records.append(run(c, c + "_home", cell, seed, args.seconds, last_exit))
            last_exit = records[-1]["ended"]
        for i in range(args.runs):                         # warm runs, alternating the copies
            seed += 101
            c = copies[i % 2]
            records.append(run(c, c + "_home", cell, seed, args.seconds, last_exit))
            last_exit = records[-1]["ended"]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(records, f)
    for r in records:
        if "phases_s" not in r:
            print("FAILED", json.dumps(r)[:1500])
        elif r["cache_files_added"]:
            print("FILES_ADDED", r["cell"], r["copy"], r["seed"], r["cache_files_added"][:12])
    for line in table(records):
        print("STUDY " + json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())

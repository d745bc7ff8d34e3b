"""What the TPU's compiler makes of lloyd_iterations at the benchmark's size,
read here, without a chip:

    python3 tools/lloyd_tpu_hlo.py [--dump DIR]

Compiles the solver at 400,000 x 3000 a chip, k=1000, chunk 32768 for a
DESCRIBED v5e (one chip, then the 2x2 mesh, both in this one process),
prints one JSON line per chip count and exits 1 if the optimised HLO breaks
what ops/kmeans.py promises:
  - no `pad` of X's width anywhere;
  - in no `while` body a copy, slice, pad, convert or transpose of its own
    that is X's width and a chunk's rows or more (inside a fusion nothing is
    written to HBM: the chunk is sliced in the products' operands);
  - of the table's size, at most one copy and one convert, both in the entry
    computation (once a fit).
The line also carries what the compiler reports and nothing holds it to: the
temporaries' bytes, the table-sized operations by name, whether an all-reduce
is there.  Nothing runs: no result and no time comes from here.  Run it
after a change to the solver and before the chip call that measures it.

Not a test of tier-1, on purpose: loading the TPU's library takes its
machine-wide lock (/tmp/libtpu_lockfile) for as long as this process lives,
so run it alone, never beside a test run or anything else that loads libtpu.
Exits 2 if no v5e topology can be described here.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_LOC, D, K, CHUNK = 400_000, 3000, 1000, 32768

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = [a-z0-9]+\[([0-9,]*)\]\S* ([a-z\-]+)\(")
_PASSES_ON = {"parameter", "get-tuple-element", "bitcast"}
_MOVES = {"copy", "dynamic-slice", "slice", "pad", "convert", "transpose"}


def instructions(text):
    """(computation, is entry, name, op, dims) of every array-valued instruction."""
    comp, entry = None, False
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp, entry = m.group(2), bool(m.group(1))
            continue
        m = _INSTRUCTION.match(line)
        if m and m.group(3) not in _PASSES_ON:
            dims = tuple(int(x) for x in m.group(2).split(",") if x)
            yield comp, entry, m.group(1), m.group(3), dims


def read_hlo(text):
    """(report, faults) of one compiled lloyd_iterations."""
    everything = list(instructions(text))
    fused = set(re.findall(r"calls=%?([\w.\-]+)", text))
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    wide = [i for i in everything if len(i[4]) == 2 and i[4][1] == D]
    own = [i for i in wide if i[0] not in fused]
    table = [i for i in own if i[4][0] >= N_LOC]
    faults = []
    if not bodies:
        faults.append("no while body found: the text is not read as it was")
    faults += [f"pad of X's width: {name} in {comp}" for comp, _, name, op, _ in wide if op == "pad"]
    faults += [
        f"{op} {name} {list(dims)} on its own in loop body {comp}"
        for comp, _, name, op, dims in own
        if comp in bodies and op in _MOVES and dims[0] >= CHUNK
    ]
    faults += [f"table-sized {op} {name} outside the entry computation, in {comp}"
               for comp, entry, name, op, _ in table if not entry]
    in_entry = [op for _, entry, _, op, _ in table if entry]
    for op in sorted(set(in_entry)):
        if op not in ("copy", "convert") or in_entry.count(op) > 1:
            faults.append(f"table-sized in the entry computation: {in_entry.count(op)} x {op}")
    report = {
        "table_sized": [f"{name} {op}" for _, _, name, op, _ in table],
        "all_reduce": "all-reduce" in text,
    }
    return report, faults


def compile_lloyd(topo, chips):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops.kmeans import lloyd_iterations
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    mesh = Mesh(np.array(topo.devices[:chips]), (DATA_AXIS,))
    rows, whole = NamedSharding(mesh, P(DATA_AXIS)), NamedSharding(mesh, P())
    X = jax.ShapeDtypeStruct((N_LOC * chips, D), jnp.float32, sharding=rows)
    w = jax.ShapeDtypeStruct((N_LOC * chips,), jnp.float32, sharding=rows)
    c = jax.ShapeDtypeStruct((K, D), jnp.float32, sharding=whole)
    return lloyd_iterations.lower(X, w, c, mesh, 30, 0.0, CHUNK).compile()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dump", help="directory to write lloyd_<chips>.hlo.txt into")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)  # such an entry cannot be read back without a chip
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except (RuntimeError, ValueError) as e:  # the plugin or its lock, not a fault of the solver
        print(f"no v5e:2x2 topology can be described here: {e}", file=sys.stderr)
        return 2
    bad = False
    for chips in (1, 4):
        compiled = compile_lloyd(topo, chips)
        text = compiled.as_text()
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump, f"lloyd_{chips}.hlo.txt"), "w") as f:
                f.write(text)
        report, faults = read_hlo(text)
        mem = compiled.memory_analysis()
        print(json.dumps({"chips": chips, "ok": not faults, "faults": faults, **report,
                          "temp_bytes": mem.temp_size_in_bytes,
                          "generated_code_bytes": mem.generated_code_size_in_bytes}))
        bad = bad or bool(faults)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

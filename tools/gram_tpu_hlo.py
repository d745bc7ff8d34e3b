"""What the TPU's compiler makes of the Gram scan at the benchmark's size, read
here, without a chip:

    python3 tools/gram_tpu_hlo.py [--dump DIR]

Compiles ops/glm.linreg_sufficient_stats at 400,000 x 3000, chunk 32768 for a
DESCRIBED v5e (one chip, then the 2x2 mesh with 100,000 rows a shard, both in
this one process), prints one JSON line per chip count and exits 1 if the
optimised HLO breaks what ops/linalg._local_moments promises:
  - every product of the walk contracts over a chunk's rows or over the rows
    left over (linalg.scan_rows), and the products of one panel, each times
    the trips of the loop it stands in, cover the shard's rows exactly: no row
    goes through a product twice, none is left out;
  - nothing of the table's size is built (a copy, pad, slice or fusion that
    writes `rows x width`), once or in the loop;
  - no array of a chunk's rows, or of the rows left over, is written to HBM on
    its own: the slice and the weighting are inside the products' fusions;
  - no mask of re-visited rows (an iota or a comparison a chunk long) and no
    `minimum` on a slice's start: the clamped last chunk is gone.
The line also carries the panels as the compiler left them (output shape,
rows of the contraction, trips) and the temporaries' bytes.  Nothing runs: no
result and no time comes from here.  Run it after a change to the scan and
before the chip call that measures it.

Not a test of tier-1, on purpose: loading the TPU's library takes its
machine-wide lock (/tmp/libtpu_lockfile) for as long as this process lives,
so run it alone, never beside a test run or anything else that loads libtpu
(tests/test_gram_rows.py holds the reader on a hand-made module instead).
Exits 2 if no v5e topology can be described here.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS, D, CHUNK = 400_000, 3000, 32768

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ([a-z0-9]+)\[([0-9,]*)\]\S* ([a-z\-]+)\((.*)$")
_PASSES_ON = {"parameter", "get-tuple-element", "bitcast"}
_PRODUCTS = {"convolution", "dot"}


def _parse(text):
    """{computation: [(name, dtype, dims, op, rest of the line)]}, the entry's name,
    {callee: caller} over fusions' `calls=` and {body: condition} over the whiles."""
    comps, entry, callers, whiles = {}, None, {}, {}
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(2)
            comps[comp] = []
            entry = comp if m.group(1) else entry
            continue
        for callee in re.findall(r"calls=%?([\w.\-]+)", line):
            callers[callee] = comp
        w = re.search(r"condition=%?([\w.\-]+), body=%?([\w.\-]+)", line)
        if w:
            whiles[w.group(2)] = w.group(1)
        m = _INSTRUCTION.match(line)
        if m and comp is not None:
            dims = tuple(int(x) for x in m.group(3).split(",") if x)
            comps[comp].append((m.group(1), m.group(2), dims, m.group(4), m.group(5)))
    return comps, entry, callers, whiles


def _trips(comps, cond):
    """A counted loop's trips: the constant its condition holds the counter under."""
    consts = {name: rest for name, _, _, op, rest in comps.get(cond, ()) if op == "constant"}
    for _, _, _, op, rest in comps.get(cond, ()):
        if op == "compare" and "direction=LT" in rest:
            for operand in re.findall(r"%([\w.\-]+)", rest.split(")")[0]):
                if operand in consts:
                    return int(re.match(r"(\d+)", consts[operand]).group(1))
    return None


def read_hlo(text, n_loc=ROWS, d=D, chunk=CHUNK):
    """(report, faults) of one compiled linreg_sufficient_stats whose shard holds n_loc rows."""
    comps, entry, callers, whiles = _parse(text)
    n_full, tail = divmod(n_loc, chunk)
    blocks = {chunk: "a chunk's", tail: "the left-over"} if tail else {chunk: "a chunk's"}
    faults = []

    def stands_in(comp):
        while comp in callers:
            comp = callers[comp]
        return comp

    own = [(c, i) for c, ins in comps.items() if c not in callers for i in ins if i[3] not in _PASSES_ON]
    for comp, (name, _, dims, op, _) in own:
        if len(dims) == 2 and dims[0] >= n_loc and dims[1] > 1:
            faults.append(f"table-sized {op} {name} {list(dims)} in {comp}")
        elif len(dims) == 2 and dims[0] in blocks and dims[1] > 1:
            faults.append(f"{op} {name} {list(dims)}, {blocks[dims[0]]} rows written on their own in {comp}")
    for comp, ins in comps.items():
        for name, dtype, dims, op, _ in ins:
            if dims == (chunk,) and (op == "iota" or (op == "compare" and dtype == "pred")):
                faults.append(f"{op} {name} a chunk long in {comp}: a mask of re-visited rows")
            if op == "minimum" and dims == () and dtype == "s32" and stands_in(comp) in whiles:
                faults.append(f"minimum {name} on an index in loop body {stands_in(comp)}: a clamped slice start")

    panels = []
    for comp, ins in comps.items():
        shapes = {name: dims for name, _, dims, _, _ in ins}
        for name, _, dims, op, rest in ins:
            if op not in _PRODUCTS or len(dims) != 2:
                continue
            ops = [shapes.get(o, ()) for o in re.findall(r"%([\w.\-]+)", rest.split(")")[0])[:2]]
            rows = ops[0][0] if len(ops) == 2 and ops[0] and ops[1] and ops[0][0] == ops[1][0] else None
            root = stands_in(comp)
            trips = _trips(comps, whiles[root]) if root in whiles else (1 if root == entry else None)
            panels.append({"out": list(dims), "rows": rows, "trips": trips, "in": root})
            if rows not in blocks:
                faults.append(f"product {name} {list(dims)} in {comp} contracts over {rows} rows: neither a chunk's nor the left-over")
            if trips is None:
                faults.append(f"product {name} in {comp}: the trips of {root} are not read")
    if not panels:
        faults.append("no product found: the text is not read as it was")
    covered = {}
    for p in panels:
        covered[tuple(p["out"])] = covered.get(tuple(p["out"]), 0) + (p["rows"] or 0) * (p["trips"] or 0)
    for out, rows in sorted(covered.items(), reverse=True):
        if rows != n_loc:
            faults.append(f"the products of panel {list(out)} cover {rows} rows a pass, the shard holds {n_loc}")
    in_loop = sorted({p["trips"] for p in panels if p["in"] in whiles})
    if in_loop != ([n_full] if n_full else []):
        faults.append(f"the loops' trips are {in_loop}, the shard holds {n_full} whole chunks")
    report = {
        "rows_a_pass": sorted(set(covered.values())),
        "panels": sorted(panels, key=lambda p: (-p["out"][1], -(p["trips"] or 0))),
        "all_reduce": "all-reduce" in text,
    }
    return report, faults


def compile_gram(topo, chips):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops.glm import linreg_sufficient_stats
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    mesh = Mesh(np.array(topo.devices[:chips]), (DATA_AXIS,))
    rows = NamedSharding(mesh, P(DATA_AXIS))
    X = jax.ShapeDtypeStruct((ROWS, D), jnp.float32, sharding=rows)
    v = jax.ShapeDtypeStruct((ROWS,), jnp.float32, sharding=rows)
    return linreg_sufficient_stats.lower(X, v, v, mesh=mesh, chunk=CHUNK).compile()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dump", help="directory to write gram_<chips>.hlo.txt into")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)  # such an entry cannot be read back without a chip
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except (RuntimeError, ValueError) as e:  # the plugin or its lock, not a fault of the scan
        print(f"no v5e:2x2 topology can be described here: {e}", file=sys.stderr)
        return 2
    bad = False
    for chips in (1, 4):
        compiled = compile_gram(topo, chips)
        text = compiled.as_text()
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump, f"gram_{chips}.hlo.txt"), "w") as f:
                f.write(text)
        report, faults = read_hlo(text, n_loc=ROWS // chips)
        mem = compiled.memory_analysis()
        print(json.dumps({"chips": chips, "rows_a_shard": ROWS // chips, "ok": not faults, "faults": faults, **report,
                          "temp_bytes": mem.temp_size_in_bytes,
                          "generated_code_bytes": mem.generated_code_size_in_bytes}))
        bad = bad or bool(faults)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

"""What the TPU's compiler makes of logistic_fit_kernel at the benchmark's size,
read here, without a chip:

    python3 tools/logistic_tpu_hlo.py [--dump DIR]

Compiles the binary fit at 400,000 x 3000 a chip, 200 iterations, for a
DESCRIBED v5e (one chip, then the 2x2 mesh, both in this one process) with the
one-pass data term on (off the chip `pallas_enabled()` is False and the kernel
would be interpreted, so this steers both: the program has no option for it),
prints one JSON line per chip count and exits 1 if the optimised HLO breaks
what ops/logistic_pass.py promises:
  - in the `while` bodies (and every computation they call) the table, in
    either orientation, is an operand of the `logistic_pass` kernel call and
    otherwise only of a slice of under a hundredth of it (the rows past the
    last whole tile, inside the plain form's fusions): one read of X an
    evaluation;
  - nowhere a table-sized pad or convert, and at most one table-sized copy or
    transpose, in the entry computation (once a fit: the layout copy the
    module had before);
  - on the mesh, one all-reduce in the line search's body.
The line also says what the compiler reports and nothing holds it to: the
temporaries' bytes, the table-sized operations by name, the kernel calls.
Nothing runs: no result and no time comes from here.  Run it after a change
to the solver and before the chip call that measures it, ALONE (loading the
TPU's library takes /tmp/libtpu_lockfile; see tools/lloyd_tpu_hlo.py).
Exits 2 if no v5e topology can be described here.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_LOC, D, ITERS = 400_000, 3000, 200
KERNEL = "logistic_pass"

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(?[a-z0-9]+\[[^=]*?) ([a-z\-]+)\((.*)$")
_PASSES_ON = {"parameter", "get-tuple-element", "bitcast", "tuple", "while", "call", "conditional"}
_MOVES = {"copy", "pad", "convert", "transpose"}
_SHAPE = re.compile(r"f32\[(\d+),(\d+)\]")


def _is_table(dims) -> bool:
    return sorted(dims) == sorted((N_LOC, D))


def computations(text):
    """{computation: (is entry, [(name, op, result dims, operand names, the line)])}."""
    out, comp = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(2)
            out[comp] = (bool(m.group(1)), [])
            continue
        m = _INSTRUCTION.match(line)
        if m and comp is not None:
            name, result, op, rest = m.groups()
            dims = [tuple(int(x) for x in s) for s in _SHAPE.findall(result)]
            operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
            out[comp][1].append((name, op, dims, operands, line))
    return out


def read_hlo(text, chips):
    """(report, faults) of one compiled logistic_fit_kernel."""
    comps = computations(text)
    faults = []
    # every computation a while body reaches, through fusions, calls and nested loops
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    if not bodies:
        faults.append("no while body found: the text is not read as it was")
    reach, frontier = set(), set(bodies)
    while frontier:
        comp = frontier.pop()
        if comp in reach or comp not in comps:
            continue
        reach.add(comp)
        for *_, line in comps[comp][1]:
            frontier.update(re.findall(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)", line))
    table_values, kernel_calls, table_sized = {}, [], []
    for comp, (entry, instrs) in comps.items():
        for name, op, dims, _, line in instrs:
            if any(_is_table(d) for d in dims):
                table_values[(comp, name)] = op
                if op not in _PASSES_ON and op != "custom-call":
                    table_sized.append((comp, entry, name, op))
            if op == "custom-call" and KERNEL in line:
                kernel_calls.append((comp, name))
    if not kernel_calls:
        faults.append(f"no {KERNEL} kernel call in the module")
    if not any(comp in reach for comp, _ in kernel_calls):
        faults.append(f"no {KERNEL} kernel call inside the while bodies")
    for comp in sorted(reach):
        for name, op, dims, operands, line in comps[comp][1]:
            # a fusion's reads are those of the computation it calls, which is in `reach`
            if op in _PASSES_ON or op == "fusion" or (op == "custom-call" and KERNEL in line):
                continue
            tail = op == "slice" and all(a * b * 100 < N_LOC * D for a, b in dims)
            if not tail and any((comp, o) in table_values for o in operands):
                faults.append(f"{op} {name} in loop computation {comp} reads the table")
    for comp, entry, name, op in table_sized:
        if op in ("pad", "convert"):
            faults.append(f"table-sized {op} {name} in {comp}")
        elif op in _MOVES and not entry:
            faults.append(f"table-sized {op} {name} outside the entry computation, in {comp}")
    moves = [op for _, entry, _, op in table_sized if entry and op in _MOVES]
    if len(moves) > 1:
        faults.append(f"{len(moves)} table-sized copies or transposes in the entry computation: {moves}")
    reduces = [c for c in sorted(reach) for _, op, *_ in comps[c][1] if op.startswith("all-reduce")]
    if chips > 1 and not reduces:
        faults.append("no all-reduce inside the while bodies on the mesh")
    report = {
        "kernel_calls": [f"{name} in {comp}" for comp, name in kernel_calls],
        "table_sized": [f"{name} {op}" for _, _, name, op in table_sized],
        "all_reduce_in_loops": len(reduces),
    }
    return report, faults


def compile_fit(topo, chips):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops import logistic, logistic_pass
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    # the chip's path, from a host whose own backend is the CPU
    logistic.pallas_enabled = lambda: True
    logistic.one_pass_sums = partial(logistic_pass.one_pass_sums, interpret=False)

    mesh = Mesh(np.array(topo.devices[:chips]), (DATA_AXIS,))
    rows = NamedSharding(mesh, P(DATA_AXIS))
    X = jax.ShapeDtypeStruct((N_LOC * chips, D), jnp.float32, sharding=rows)
    y = jax.ShapeDtypeStruct((N_LOC * chips,), jnp.int32, sharding=rows)
    w = jax.ShapeDtypeStruct((N_LOC * chips,), jnp.float32, sharding=rows)
    return logistic.logistic_fit_kernel.lower(
        X, y, w, 1, 1e-5, 0.0, True, ITERS, 1e-30, False, mesh
    ).compile()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dump", help="directory to write logistic_<chips>.hlo.txt into")
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
        compiled = compile_fit(topo, chips)
        text = compiled.as_text()
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump, f"logistic_{chips}.hlo.txt"), "w") as f:
                f.write(text)
        report, faults = read_hlo(text, chips)
        mem = compiled.memory_analysis()
        print(json.dumps({"chips": chips, "ok": not faults, "faults": faults, **report,
                          "temp_bytes": mem.temp_size_in_bytes,
                          "generated_code_bytes": mem.generated_code_size_in_bytes}))
        bad = bad or bool(faults)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

"""What the TPU's compiler makes of KMeans on a feature-major table at the
benchmark's size, read here, without a chip:

    python3 tools/lloyd_tall_tpu_hlo.py [--dump DIR]

Compiles `random_init_tall` and `lloyd_tall` at 25,000,000 x 30 a chip (xt of
(32, rows)), k=20, chunk 32768 for a DESCRIBED v5e (one chip, then the 2x2 mesh,
both in this one process), the update pass's Pallas kernel `lloyd_tall_pass`
compiled by Mosaic as the chip runs it (this process's backend is the CPU, where
the solver would take the interpreter: the tool steers
`ops.lloyd_tall_pass.interpreted`), prints one JSON line per executable with its
temporaries and exits 1 if
  - the compiler keeps more than half the table beside it (on the mesh the seeded
    draw's top_k gathers the rows' 100M keys, 0.8 GB a chip; both traps PR 52
    fell into show here as temporaries of the table's size or four times it: a
    gather along the lanes, or a slice one lane wide, lays the table out row-major
    first, 12.8 GB; a dynamic slice of a sharded table gathers all of it on every
    device, where the compile then fails for memory);
  - a loop body of the solver holds a `copy`, `transpose` or `pad` of a chunk's
    width on its own (inside a fusion nothing is written to HBM; the line also
    counts the module's products: the left-over block's two and the inertia
    pass's, the update's whole tiles being the kernel's);
  - the solver holds no call of the kernel (the line counts them: one, in the
    update's loop body);
  - on the mesh the solver has no all-reduce.
Nothing runs: no result and no time comes from here.  Run it by hand after a
change to ops/tall.py or to the tall half of ops/kmeans.py, before the chip call
that measures it, and ALONE (loading the TPU's library takes
/tmp/libtpu_lockfile; see tools/lloyd_tpu_hlo.py).  Exits 2 if no v5e topology
can be described here.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_LOC, COLS, K, CHUNK = 25_000_000, 30, 20, 32768


def loop_faults(text: str) -> list:
    """What an update's or the inertia pass's loop body must not hold."""
    faults = []
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    if not bodies:
        return ["no while body found: the text is not read as it was"]
    for block in text.split("\n\n"):
        head = re.match(r"^%?([\w.\-]+) \(", block.strip())
        if not head or head.group(1) not in bodies:
            continue
        for line in block.splitlines()[1:]:
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = f32\[(\d+),(\d+)\]\S* (copy|transpose|pad)\(", line)
            if m and int(m.group(3)) >= CHUNK:
                faults.append(f"{m.group(4)} {m.group(1)} [{m.group(2)},{m.group(3)}] on its own in loop body {head.group(1)}")
    return faults


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dump", help="directory to write <name>_<chips>.hlo.txt into")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)  # such an entry cannot be read back without a chip
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops import lloyd_tall_pass
    from spark_rapids_ml_tpu.ops.kmeans import lloyd_tall, random_init_tall
    from spark_rapids_ml_tpu.ops.tall import TallMatrix, padded_features
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except (RuntimeError, ValueError) as e:  # the plugin or its lock, not a fault of the solver
        print(f"no v5e:2x2 topology can be described here: {e}", file=sys.stderr)
        return 2
    # this process's backend is the CPU: the kernel is compiled as the chip runs it
    lloyd_tall_pass.interpreted = lambda: False
    bad = False
    for chips in (1, 4):
        mesh = Mesh(np.array(topo.devices[:chips]), (DATA_AXIS,))
        shard = lambda *spec: NamedSharding(mesh, P(*spec))      # noqa: E731
        X = TallMatrix(jax.ShapeDtypeStruct((padded_features(COLS), N_LOC * chips), jnp.float32, sharding=shard(None, DATA_AXIS)), COLS)
        w = jax.ShapeDtypeStruct((N_LOC * chips,), jnp.float32, sharding=shard(DATA_AXIS))
        c = jax.ShapeDtypeStruct((K, COLS), jnp.float32, sharding=shard())
        table = X.resident_bytes // chips
        for name, lower in (
            ("random_init_tall", lambda: random_init_tall.lower(X, w, K, 5, mesh)),
            ("lloyd_tall", lambda: lloyd_tall.lower(X, w, c, mesh, 30, 0.0, CHUNK)),
        ):
            try:
                compiled = lower().compile()
            except Exception as e:      # the compiler's refusal (memory) is the finding
                print(json.dumps({"what": name, "chips": chips, "ok": False, "faults": [str(e).splitlines()[0][:300]]}))
                bad = True
                continue
            text, mem = compiled.as_text(), compiled.memory_analysis()
            if args.dump:
                os.makedirs(args.dump, exist_ok=True)
                with open(os.path.join(args.dump, f"{name}_{chips}.hlo.txt"), "w") as f:
                    f.write(text)
            faults = []
            if mem.temp_size_in_bytes > table // 2:
                faults.append(f"temporaries of {mem.temp_size_in_bytes} bytes beside a table of {table}")
            kernels = len(re.findall(r"^\s*(?:ROOT )?%?lloyd_tall_pass[\w.\-]* = .* custom-call\(.*tpu_custom_call", text, re.M))
            if name == "lloyd_tall":
                faults += loop_faults(text)
                if not kernels:
                    faults.append("no call of the kernel lloyd_tall_pass")
                if chips > 1 and "all-reduce" not in text:
                    faults.append("no all-reduce on the mesh")
            print(json.dumps({"what": name, "chips": chips, "ok": not faults, "faults": faults,
                              "temp_bytes": mem.temp_size_in_bytes, "table_bytes_a_chip": table,
                              "products": text.count(" convolution("), "kernel_calls": kernels}))
            bad = bad or bool(faults)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

"""Compile the one-chip forest builder's level steps at the benchmark's real sizes
(rf_clf_fit: 50 trees x 400,000 x 3000, depth 13, 128 bins; rf_reg_fit: 30 trees, depth 6,
1000 features a split, the label's two products: names that start with reg_; rf_higgs_fit: 25 trees x
2,750,000 x 28, every node its own 5 columns: names that start with higgs_) for a DESCRIBED v5e:
the TPU's compiler is installed here, nothing runs.  Prints, per executable,
whether Mosaic and XLA took it, how long the compile ran, and the compiler's
account of its temporaries: what the chip's compiler would refuse (a block not
aligned to the tiling, scoped VMEM, HBM) is refused here, at no chip time.

    JAX_PLATFORMS=cpu python3 tools/forest_tpu_compile.py [name-part ...]

Run it by hand after a change to ops/forest_mxu.py or ops/forest_hist.py, before
the chip call, and ALONE: loading libtpu takes the machine-wide lock file (which
is why it is a tool and no tier-1 test).  A compile that passes is not a chip run."""
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from spark_rapids_ml_tpu.ops import forest_mxu as fm
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
def A(shape, dt): return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one)
T, n, D, S, F, B, depth = 50, 400000, 3000, 2, 54, 128, 13
n_pad = -(-n // 2048) * 2048
M = 2 ** (depth + 1) - 1; C = 5 + S
f_pad = 64; nb = 128; n2 = fm._deep_width(n_pad, nb); P = 14; n_tiles = n2 // 512
print("n_pad", n_pad, "n2", n2, "tiles", n_tiles)
f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8
which = sys.argv[1:] or ["all"]
def go(name, fn, *avals, **st):
    if which != ["all"] and not any(w in name for w in which): return
    t0 = time.time()
    try:
        c = fn.lower(*avals, **st).compile()
        ma = c.memory_analysis()
        # a payload sort: the arrays its `sort` carries (the key, the payloads; a stable one an iota besides)
        sort = "".join(
            f" sort_operands={len(ops.split(','))} stable={'is_stable=true' in rest}"
            for ops, rest in re.findall(r" sort\(([^)]*)\)(.*)", c.as_text())
        )
        print(f"{name}: ok {time.time()-t0:.1f}s temp={ma.temp_size_in_bytes/2**20:.0f}MB out={ma.output_size_in_bytes/2**20:.0f}MB args={ma.argument_size_in_bytes/2**20:.0f}MB{sort}", flush=True)
    except Exception as e:
        print(f"{name}: FAILED {time.time()-t0:.1f}s {str(e)[:1500]}", flush=True)
a_rel, a_buf, a_w, a_t0 = A((T, n_pad), i32), A((C, T, M), f32), A((T, n_pad), f32), A((), i32)
for level in (0, 3, 6):
    nodes = 2 ** level; tpack = fm._even_chunk(T, 128 // (nodes * S))
    go(f"shallow_step_l{level}", fm._shallow_step, a_rel, a_buf, a_w, A((S, n_pad), f32), A((f_pad, n_pad), i8), a_t0,
       tpack=tpack, nodes=nodes, s_dim=S, kind="gini", n_bins=B, F=F, msl=1.0, mid=0.0, interpret=False)
go("deep_layout", fm._deep_layout, a_rel, a_w, n_buckets=nb, n2=n2)
go("deep_state", fm._deep_state, tuple(A((T, n2), i32) for _ in range(P)), A((T, n2), f32), A((T, n2), f32), f_pad=f_pad, s_dim=S, kind="gini")
a_bins, a_loc, a_st, a_seg = A((T, f_pad, n2), i8), A((T, 1, n2), i32), A((T, S, n2), f32), A((T, n_tiles), i32)
for level in (7, 10, 12):
    tc = fm._deep_chunk(T, "gini", nb, f_pad, 2 ** (level - 7) * S, B)
    go(f"deep_step_l{level}_tc{tc}", fm._deep_step, a_bins, a_loc, a_st, a_st, a_seg, a_buf, a_t0,
       t_chunk=tc, level=level, bucket_level=7, s_dim=S, kind="gini", n_bins=B, F=F, msl=1.0, mid=0.0, interpret=False)
go("deep_leaf", fm._deep_leaf, a_loc, a_st, a_seg, a_buf, level=13, bucket_level=7, kind="gini")
def go_sorts(prefix, a_pays):
    """The deep phase's payload sorts as the program groups them (fm._sort_groups): an executable a distinct group."""
    groups = [tuple(a_pays[g0:g1]) for g0, g1 in fm._sort_groups(len(a_pays))]
    for group in dict.fromkeys(groups):
        go(prefix + fm._sort_name(group), fm._sort_part, a_rel, A((T, n2 - n_pad), i32), group, n_buckets=nb, n2=n2)
go_sorts("", [A((T, n_pad), i32)] * P + [a_w, A((n_pad,), f32)])
from spark_rapids_ml_tpu.ops.forest_hist import _gather_blocks, gather_rows_matmul, tile_feature_rows
def table(rows):  # the binned table, a feature a slice of whole tiles (tile_feature_rows)
    per, blocks = _gather_blocks(rows)
    return A((D, per * blocks, 32, 128), i8)
go("tile_feature_rows", tile_feature_rows, A((D, n_pad), i8))
go("gather", gather_rows_matmul, table(n_pad), A((F,), i32), f_pad=f_pad, n_pad=n_pad)
go("pack_all", fm._pack_all, table(n_pad), A((T, F), i32), n_pad=n_pad, P=P, interpret=False)
# an odd number of 2048-row tiles (the last gather block partial) and the widest block (16 tiles a feature)
for rows in (197 * 2048, 3 * 2048, 32 * 4096):
    go(f"gather_rows{rows}", gather_rows_matmul, table(rows), A((F,), i32), f_pad=f_pad, n_pad=rows)

# rf_reg_fit: a regressor's steps (two products a feature, 1024 subset rows) and its gather
T, F, depth, f_pad = 30, 1000, 6, 1024
M = 2 ** (depth + 1) - 1; C = 6
a_rel, a_buf, a_w = A((T, n_pad), i32), A((C, T, M), f32), A((T, n_pad), f32)
go("reg_gather", gather_rows_matmul, table(n_pad), A((F,), i32), f_pad=f_pad, n_pad=n_pad)
for level in (0, 2, 5):
    nodes = 2 ** level; tpack = fm._even_chunk(T, 128 // (nodes * 2))
    go(f"reg_shallow_step_l{level}", fm._shallow_step, a_rel, a_buf, a_w, A((3, n_pad), f32), A((f_pad, n_pad), i8), a_t0,
       tpack=tpack, nodes=nodes, s_dim=2, kind="regression", n_bins=B, F=F, msl=1.0, mid=0.0, interpret=False)
go("reg_shallow_leaf", fm._shallow_leaf, a_rel, a_buf, a_w, A((3, n_pad), f32), a_t0, tpack=1, nodes=64, kind="regression")

# rf_higgs_fit: 25 trees x 2,750,000 x 28, depth 13; every scan over the whole table (one feature
# block), every node its own 5 columns (names that start with higgs_)
T, n, D, F, subset, depth, f_pad, P = 25, 2_750_000, 28, 28, 5, 13, 32, 7
n_pad = -(-n // 2048) * 2048
M = 2 ** (depth + 1) - 1; C = 5 + S
n2 = fm._deep_width(n_pad, nb); n_tiles = n2 // 512
a_rel, a_buf, a_w, a_seed = A((T, n_pad), i32), A((C, T, M), f32), A((T, n_pad), f32), A((), jnp.uint32)
for level in (0, 3, 6):
    nodes = 2 ** level; tpack = fm._even_chunk(T, 128 // (nodes * S))
    go(f"higgs_shallow_step_l{level}", fm._shallow_step, a_rel, a_buf, a_w, A((S, n_pad), f32), A((f_pad, n_pad), i8), a_t0, a_seed,
       tpack=tpack, nodes=nodes, s_dim=S, kind="gini", n_bins=B, F=F, msl=1.0, mid=0.0, interpret=False, subset=subset)
go("higgs_deep_layout", fm._deep_layout, a_rel, a_w, n_buckets=nb, n2=n2)
go("higgs_deep_state", fm._deep_state, tuple(A((T, n2), i32) for _ in range(P)), A((T, n2), f32), A((T, n2), f32), f_pad=f_pad, s_dim=S, kind="gini")
a_bins, a_loc, a_st, a_seg = A((T, f_pad, n2), i8), A((T, 1, n2), i32), A((T, S, n2), f32), A((T, n_tiles), i32)
for level in (7, 10, 12):
    tc = fm._deep_chunk(T, "gini", nb, f_pad, 2 ** (level - 7) * S, B)
    go(f"higgs_deep_step_l{level}_tc{tc}", fm._deep_step, a_bins, a_loc, a_st, a_st, a_seg, a_buf, a_t0, a_seed,
       t_chunk=tc, level=level, bucket_level=7, s_dim=S, kind="gini", n_bins=B, F=F, msl=1.0, mid=0.0, interpret=False, subset=subset)
go("higgs_deep_leaf", fm._deep_leaf, a_loc, a_st, a_seg, a_buf, level=13, bucket_level=7, kind="gini")
go_sorts("higgs_", [A((n_pad,), i32)] * P + [a_w, A((n_pad,), f32)])
go("higgs_gather", gather_rows_matmul, table(n_pad), A((D,), i32), f_pad=f_pad, n_pad=n_pad)
go("higgs_pack_all", fm._pack_all, table(n_pad), A((1, D), i32), n_pad=n_pad, P=P, interpret=False)

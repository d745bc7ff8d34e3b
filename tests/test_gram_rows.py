"""Which rows a trip of the chunk scan is handed (ops/linalg._local_moments, PR 50).

The walk takes linalg.scan_rows' whole chunks where they lie, by a loop over the
chunk index, and the rows left over as one block of their own shape: every row of
a shard goes through the products once.  Before PR 50 the last chunk's start was
clamped to n_loc - chunk and the rows it had seen before went through the products
again under a weight of zero (25,984 of 425,984 at the benchmark's 400,000 rows).

Held here: every moment against the weighted product in float64 over shards of
0, 1 and 3 whole chunks with 0, 1 and chunk - 1 rows left over, on one device and
over a mesh, with labels and without, at one panel and at several, with an
outlier row of weight zero in a whole chunk and in the rows left over; the same
table walked as ONE block; the traced program's products (their rows are a
chunk's or the left-over's and, times their loop's trips, add up to the shard's
rows exactly; no mask of re-visited rows, no clamp of a slice's start; a shard of
whole chunks traces no left-over block, a shorter one no loop): those fail at
PR 49.  And tools/gram_tpu_hlo.py's reader, which holds the same of the TPU
compiler's text, on a hand-made module (no compiler runs here)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr

from spark_rapids_ml_tpu.ops import glm, linalg
from spark_rapids_ml_tpu.parallel.mesh import get_mesh

CHUNK = 32
SHARDS = [(n_full, rem) for n_full in (0, 1, 3) for rem in (0, 1, CHUNK - 1) if n_full or rem]
_ids = [f"{n_full}x{CHUNK}+{rem}" for n_full, rem in SHARDS]


def _table(n_loc, d, n_dev, seed=0):
    """n_dev shards of n_loc rows; in every shard a row of weight zero that would
    wreck the sums if it counted, in the first whole chunk and in the rows left over
    (where more than one row is left over: a shard of one row keeps its weight)."""
    rng = np.random.default_rng(seed + 1000 * n_loc + d)
    n = n_loc * n_dev
    X = rng.standard_normal((n, d)).astype(np.float32)
    w = rng.uniform(0.5, 2.0, n).astype(np.float32)
    w[2::5] = 0.0
    y = rng.standard_normal(n).astype(np.float32)
    n_full, rem = divmod(n_loc, CHUNK)
    for shard in range(n_dev):
        at = [shard * n_loc + 3] * bool(n_full) + [shard * n_loc + n_loc - 1] * (rem > 1)
        X[at], y[at], w[at] = 1e4, -1e4, 0.0
    return X, w, y


def _moments(X, w, y, n_dev, chunk):
    Xd, wd = jnp.asarray(X), jnp.asarray(w)
    if n_dev == 1:
        out = linalg._local_moments(Xd, wd, chunk, y_loc=None if y is None else jnp.asarray(y))
    elif y is not None:
        s = glm.linreg_sufficient_stats(Xd, jnp.asarray(y), wd, mesh=get_mesh(n_dev), chunk=chunk)
        out = (s.wsum, s.x_mean * s.wsum, s.G, s.y_mean * s.wsum, s.c, s.y2)
    else:
        wsum, mean, G = linalg._sharded_moments(Xd, wd, get_mesh(n_dev), chunk)
        out = (wsum, mean * wsum, G)
    return jax.device_get(out)


@pytest.mark.parametrize("n_dev", [1, 2])
@pytest.mark.parametrize("with_y", [True, False], ids=["labels", "no_labels"])
@pytest.mark.parametrize("d", [8, linalg.GRAM_PANEL_WIDTH + 1])
@pytest.mark.parametrize("n_full,rem", SHARDS, ids=_ids)
def test_every_row_counts_once_whatever_the_shard_leaves_over(n_full, rem, d, with_y, n_dev):
    n_loc = n_full * CHUNK + rem
    X, w, y = _table(n_loc, d, n_dev)
    out = _moments(X, w, y if with_y else None, n_dev, CHUNK)
    X64, w64, y64 = X.astype(np.float64), w.astype(np.float64), y.astype(np.float64)
    Xw = X64 * w64[:, None]
    want = [w64.sum(), Xw.sum(0), Xw.T @ X64] + ([(y64 * w64).sum(), Xw.T @ y64, (y64 * y64 * w64).sum()] if with_y else [])
    assert len(out) == len(want) and out[2].shape == (d, d)
    rows = len(w)
    for got, ref in zip(out, want):
        # float32 sums of `rows` terms of size ~|ref| / rows .. 4: nowhere near the outlier's 1e8
        np.testing.assert_allclose(got, ref, rtol=2e-6, atol=1e-5 * rows ** 0.5)
    # ONE block (a chunk no shorter than the shard): the same sums in another order
    whole = _moments(X, w, y if with_y else None, n_dev, max(n_loc, CHUNK))
    for got, ref in zip(out, whole):
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5 * rows ** 0.5)


# -- the traced program ---------------------------------------------------------

def _inner(value):
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _inner(v)


def _equations(jaxpr, trips=1, in_loop=False):
    """(equation, trips of the loops around it, whether a loop is around it), all the way down."""
    for eqn in jaxpr.eqns:
        yield eqn, trips, in_loop
        loop = eqn.primitive.name in ("scan", "while")
        inner_trips = trips * eqn.params["length"] if eqn.primitive.name == "scan" else trips
        for value in eqn.params.values():
            for inner in _inner(value):
                yield from _equations(inner, inner_trips, in_loop or loop)


def _trace(n_loc, d, with_y, n_dev=1):
    X = jax.ShapeDtypeStruct((n_loc * n_dev, d), jnp.float32)
    v = jax.ShapeDtypeStruct((n_loc * n_dev,), jnp.float32)
    if n_dev == 1:
        traced = jax.make_jaxpr(lambda X, w, y: linalg._local_moments(X, w, CHUNK, y_loc=y if with_y else None))(X, v, v)
    else:
        traced = jax.make_jaxpr(lambda X, w, y: glm.linreg_sufficient_stats(X, y, w, mesh=get_mesh(n_dev), chunk=CHUNK))(X, v, v)
    return list(_equations(traced.jaxpr))


def _products(eqns):
    """(rows of the contraction, output shape, trips, in a loop) of every dot_general."""
    out = []
    for eqn, trips, in_loop in eqns:
        if eqn.primitive.name == "dot_general":
            (lhs_contract, _), _ = eqn.params["dimension_numbers"]
            rows, = (eqn.invars[0].aval.shape[a] for a in lhs_contract)
            out.append((rows, eqn.outvars[0].aval.shape, trips, in_loop))
    return out


# over a mesh the traced pass is linreg_sufficient_stats, which has labels
@pytest.mark.parametrize(
    "d,with_y,n_dev",
    [(8, True, 1), (linalg.GRAM_PANEL_WIDTH + 1, True, 1), (3000, False, 1), (8, True, 2), (linalg.GRAM_PANEL_WIDTH + 1, True, 2)],
    ids=["d8", "d257", "d3000_no_labels", "d8_mesh", "d257_mesh"],
)
@pytest.mark.parametrize("n_full,rem", SHARDS, ids=_ids)
def test_the_traced_products_cover_the_shards_rows_exactly_once(n_full, rem, d, with_y, n_dev):
    n_loc = n_full * CHUNK + rem
    eqns = _trace(n_loc, d, with_y, n_dev)
    products = _products(eqns)
    assert len(products) == (linalg.gram_panels(d) + with_y) * (bool(n_full) + bool(rem))
    assert {rows for rows, *_ in products} == ({CHUNK} if n_full else set()) | ({rem} if rem else set())
    covered = {}
    for rows, shape, trips, _ in products:
        covered[shape] = covered.get(shape, 0) + rows * trips
    assert set(covered.values()) == {n_loc}, covered
    # the loop holds the whole chunks and nothing else; the rows left over stand outside it
    assert all(in_loop == (rows == CHUNK and bool(n_full)) and trips == (n_full if in_loop else 1) for rows, _, trips, in_loop in products), products
    loops = [eqn for eqn, _, _ in eqns if eqn.primitive.name in ("scan", "while")]
    assert len(loops) == bool(n_full)
    # no clamp of a slice's start, and inside the loop no mask of re-visited rows
    names = [eqn.primitive.name for eqn, _, _ in eqns]
    assert "min" not in names and "clamp" not in names
    # (PR 49's was `start + iota >= i * chunk`; a traced slice start brings its own `lt` and `select_n`)
    assert not {"iota", "ge"} & {eqn.primitive.name for eqn, _, in_loop in eqns if in_loop}
    slices = [eqn for eqn, _, _ in eqns if eqn.primitive.name == "dynamic_slice"]
    assert slices and all(eqn.outvars[0].aval.shape[0] in (CHUNK, rem) for eqn in slices)


def test_the_plan_is_whole_chunks_and_the_rows_left_over():
    assert linalg.scan_rows(400_000, 32768) == (12, 6784)
    assert linalg.scan_rows(100_000, 32768) == (3, 1696)
    assert linalg.scan_rows(32768, 32768) == (1, 0) and linalg.scan_rows(4096, 32768) == (0, 4096)


# -- tools/gram_tpu_hlo.py's reader, on a hand-made module ------------------------

_PANEL = """
%slice_w.{tag} (p0: f32[400000,3000], p1: s32[], p2: f32[{rows}]) -> f32[{rows},256] {{
  %p0 = f32[400000,3000]{{0,1:T(8,128)}} parameter(0)
  %p1 = s32[]{{:T(128)}} parameter(1)
  %z = s32[]{{:T(128)}} constant(0)
  %ds = f32[{rows},256]{{0,1:T(8,128)}} dynamic-slice(%p0, %p1, %z), dynamic_slice_sizes={{{rows},256}}
  %p2 = f32[{rows}]{{0:T(1024)}} parameter(2)
  %b = f32[{rows},256]{{0,1:T(8,128)}} broadcast(%p2), dimensions={{0}}
IN_PANEL
  ROOT %m = f32[{rows},256]{{0,1:T(8,128)}} multiply(%ds, %b)
}}

%slice_x.{tag} (p0: f32[400000,3000], p1: s32[]) -> f32[{rows},3000] {{
  %p0 = f32[400000,3000]{{0,1:T(8,128)}} parameter(0)
  %p1 = s32[]{{:T(128)}} parameter(1)
  %z = s32[]{{:T(128)}} constant(0)
  ROOT %ds = f32[{rows},3000]{{0,1:T(8,128)}} dynamic-slice(%p0, %p1, %z), dynamic_slice_sizes={{{rows},3000}}
}}

%panel.{tag} (acc: f32[256,3000], X: f32[400000,3000], i: s32[], w: f32[{rows}]) -> f32[256,3000] {{
  %acc = f32[256,3000]{{0,1:T(8,128)}} parameter(0)
  %X = f32[400000,3000]{{0,1:T(8,128)}} parameter(1)
  %i = s32[]{{:T(128)}} parameter(2)
  %w = f32[{rows}]{{0:T(1024)}} parameter(3)
  %lhs = f32[{rows},256]{{0,1:T(8,128)}} fusion(%X, %i, %w), kind=kLoop, calls=%slice_w.{tag}
  %rhs = f32[{rows},3000]{{0,1:T(8,128)}} fusion(%X, %i), kind=kLoop, calls=%slice_x.{tag}
  %convolution.{tag} = f32[256,3000]{{0,1:T(8,128)}} convolution(%lhs, %rhs), dim_labels=fb_io->bf, operand_precision={{highest,highest}}
  ROOT %add = f32[256,3000]{{0,1:T(8,128)}} add(%acc, %convolution.{tag})
}}
"""

_REST = """
%cond.1 (arg: (s32[], f32[256,3000])) -> pred[] {
  %arg = (s32[]{:T(128)}, f32[256,3000]{0,1:T(8,128)}) parameter(0)
  %trips = s32[]{:T(128)} constant(TRIPS)
  %gte = s32[]{:T(128)} get-tuple-element(%arg), index=0
  ROOT %lt = pred[]{:T(512)} compare(%gte, %trips), direction=LT
}

%body.1 (arg: (s32[], f32[256,3000])) -> (s32[], f32[256,3000]) {
  %arg = (s32[]{:T(128)}, f32[256,3000]{0,1:T(8,128)}) parameter(0)
  %gte.0 = s32[]{:T(128)} get-tuple-element(%arg), index=0
  %gte.1 = f32[256,3000]{0,1:T(8,128)} get-tuple-element(%arg), index=1
  %X = f32[400000,3000]{0,1:T(8,128)} parameter(1)
  %w = f32[32768]{0:T(1024)} parameter(2)
  %chunk = s32[]{:T(128)} constant(32768)
  %start = s32[]{:T(128)} multiply(%gte.0, %chunk)
IN_BODY
  %select_add_fusion.29 = f32[256,3000]{0,1:T(8,128)} fusion(%gte.1, %X, %start, %w), kind=kOutput, calls=%panel.loop
  ROOT %tuple.1 = (s32[]{:T(128)}, f32[256,3000]{0,1:T(8,128)}) tuple(%gte.0, %select_add_fusion.29)
}

ENTRY %main.1 (X.1: f32[400000,3000], w.1: f32[400000]) -> f32[256,3000] {
  %X.1 = f32[400000,3000]{0,1:T(8,128)} parameter(0)
  %w.1 = f32[400000]{0:T(1024)} parameter(1)
IN_ENTRY
  %while.1 = (s32[]{:T(128)}, f32[256,3000]{0,1:T(8,128)}) while(%tuple.0), condition=%cond.1, body=%body.1
  %gte.5 = f32[256,3000]{0,1:T(8,128)} get-tuple-element(%while.1), index=1
TAIL}
"""

_TAIL = """  %at = s32[]{:T(128)} constant(393216)
  %w.tail = f32[6784]{0:T(1024)} slice(%w.1), slice={[393216:400000]}
  ROOT %fusion.119 = f32[256,3000]{0,1:T(8,128)} fusion(%gte.5, %X.1, %at, %w.tail), kind=kOutput, calls=%panel.tail
"""


def _module(trips, in_panel="", in_body="", in_entry="", tail=True):
    """A compiled scan as the TPU's compiler prints it, cut to one panel: the loop's
    fusion over a chunk's rows, and (tail) the entry's fusion over the rows left over."""
    text = _PANEL.format(tag="loop", rows=32768) + (_PANEL.format(tag="tail", rows=6784).replace("IN_PANEL\n", "") if tail else "") + _REST
    text = text.replace("TRIPS", str(trips)).replace("TAIL", _TAIL if tail else "  ROOT %copy.1 = f32[256,3000]{0,1:T(8,128)} copy(%gte.5)\n")
    for mark, put in (("IN_PANEL\n", in_panel), ("IN_BODY\n", in_body), ("IN_ENTRY\n", in_entry)):
        text = text.replace(mark, put + "\n" if put else "")
    return text


@pytest.mark.parametrize(
    "trips,in_panel,in_body,in_entry,faults",
    [
        (12, "", "", "", 0),
        # PR 49's walk: a thirteenth trip over a clamped start, its seen rows masked
        (13, "  %iota.20 = s32[32768]{0:T(1024)} iota(), iota_dimension=0\n  %ge.10 = pred[32768]{0:T(1024)(128)(4,1)} compare(%iota.20, %b2), direction=GE",
         "  %min.4 = s32[]{:T(128)} minimum(%start, %last)", "", 5),
        (11, "", "", "", 2),
        (12, "", "  %copy.9 = f32[32768,3000]{0,1:T(8,128)} copy(%x)", "", 1),
        (12, "", "  %weighted.7 = f32[32768,256]{0,1:T(8,128)} fusion(%X, %start, %w), kind=kLoop, calls=%slice_w.loop", "", 1),
        (12, "", "", "  %slice.3 = f32[6784,3000]{0,1:T(8,128)} slice(%X.1), slice={[393216:400000], [0:3000]}", 1),
        (12, "", "", "  %copy.4 = f32[400000,3000]{1,0:T(8,128)} copy(%X.1)", 1),
        (12, "", "", "  %pad.6 = f32[425984,3000]{0,1:T(8,128)} pad(%X.1, %c), padding=0_25984x0_0", 1),
    ],
    ids=["clean", "clamped_last_chunk", "a_chunk_left_out", "chunk_copy_in_loop", "weighted_chunk_written", "tail_slice_written", "table_copy", "table_pad"],
)
def test_tpu_hlo_reader_names_the_rows_that_went_through_twice(trips, in_panel, in_body, in_entry, faults):
    from tools.gram_tpu_hlo import read_hlo

    report, found = read_hlo(_module(trips, in_panel, in_body, in_entry))
    assert len(found) == faults, found
    assert report["rows_a_pass"] == [trips * 32768 + 6784]
    assert [(p["out"], p["rows"], p["trips"]) for p in report["panels"]] == [([256, 3000], 32768, trips), ([256, 3000], 6784, 1)]


@pytest.mark.parametrize("n_loc,tail,faults", [(393216, False, 0), (400000, False, 1), (393216, True, 2)], ids=["whole_chunks", "left_over_rows_dropped", "a_block_too_many"])
def test_tpu_hlo_reader_holds_the_left_over_block_to_the_shards_rows(n_loc, tail, faults):
    from tools.gram_tpu_hlo import read_hlo

    report, found = read_hlo(_module(12, tail=tail), n_loc=n_loc)
    assert len(found) == faults, found
    assert report["rows_a_pass"] == [393216 + 6784 * tail]

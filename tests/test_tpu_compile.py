"""The main path's device programs, compiled for a described TPU v5e at
ML-20M width — no chip attached, nothing runs.

The TPU's compiler is installed beside JAX and compiles for a topology
that is described, not attached. What it refuses here (a program that
does not fit 16 GB of HBM, a sharding it cannot partition) the chip would
refuse too, so these guard every later PR at no chip time. A compile
that passes is not a chip run: ``chip_smoke.py`` is that.

Shapes are the recommendation template's at the ML-20M size the smoke
trains (138,493 users x 26,744 items, rank 32, 20M observations:
segment width 128, 8 + 6 chunks of 32,768 segments).

The topology is described inside a module-scoped fixture and nowhere
else: only one process at a time may load the TPU's library, and under
pytest-xdist every worker imports this file, so touching ``topologies``
at import (or in a ``skipif`` / ``parametrize``) would break collection
for the whole suite. The persistent compile cache is off around these
compiles — an entry compiled for a described device is written but can
never be read back without the chip.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from predictionio_tpu.ops import als, retrieval
from predictionio_tpu.parallel.mesh import pad_to_multiple

N_USERS, N_ITEMS, RANK = 138_493, 26_744, 32
CHUNKS_U, CHUNKS_I, SC, L = 8, 6, 32_768, 128
BATCH = 128
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("data",))


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _loop_args(n_shards, row, seg2, seg3, scalar):
    """Arguments of ``_run_iterations`` as ``train_als`` /
    ``train_from_wire`` place them: factors, lam and has_obs row-sharded,
    the packs sharded on the segment dim."""
    r_u = als._padded_rows(N_USERS, n_shards)
    r_i = als._padded_rows(N_ITEMS, n_shards)

    def pack(chunks):
        return (
            _shape((chunks, SC), jnp.int32, seg2),
            _shape((chunks, SC, L), jnp.int32, seg3),
            _shape((chunks, SC, L), jnp.float32, seg3),
            _shape((chunks, SC), jnp.int32, seg2),
        )

    return (
        _shape((r_u, RANK), jnp.float32, row),
        _shape((r_i, RANK), jnp.float32, row),
        pack(CHUNKS_U), pack(CHUNKS_I),
        _shape((r_u,), jnp.float32, row),
        _shape((r_i,), jnp.float32, row),
        _shape((r_u,), jnp.bool_, row),
        _shape((r_i,), jnp.bool_, row),
        1.0,
        _shape((), jnp.int32, scalar),
    )


def _device_bytes(compiled) -> int:
    """Bytes one device must hold to run the program."""
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )


@pytest.mark.parametrize(
    "solver,block_size", [("exact", 0), ("subspace", 8)]
)
def test_fused_loop_float32_fits_one_chip(one_chip, solver, block_size):
    """What ``pio train`` runs on one chip (no engine passes
    ``compute_dtype``, so float32), for both solvers."""
    args = _loop_args(1, one_chip, one_chip, one_chip, one_chip)
    compiled = als._run_iterations.lower(
        *args, implicit=False, compute_dtype="float32",
        rep_sharding=None, row_sharding=None, telemetry=True,
        solver=solver, block_size=block_size,
    ).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_fused_loop_shards_over_four_chips(mesh4):
    """The sharded ``train_als`` program: per-device memory must be a
    fraction of the one-chip program's (nothing store-sized replicated
    or left whole on device 0), with the factor handoff compiled to
    collectives."""
    row = NamedSharding(mesh4, P("data"))
    rep = NamedSharding(mesh4, P())
    args = _loop_args(
        4, row,
        NamedSharding(mesh4, P(None, "data")),
        NamedSharding(mesh4, P(None, "data", None)),
        rep,
    )
    compiled = als._run_iterations.lower(
        *args, implicit=False, compute_dtype="float32",
        rep_sharding=rep, row_sharding=row, telemetry=True,
        solver="exact", block_size=0,
    ).compile()
    # the one-chip program needs ~8.8 GB; a quarter of it plus the
    # replicated counter-side factors stays well under 4 GiB
    assert _device_bytes(compiled) < 4 * 2**30
    assert re.search(r"\ball-gather", compiled.as_text())


def test_serving_topn_compiles(one_chip):
    compiled = als._topn_packed.lower(
        _shape((BATCH, RANK), jnp.float32, one_chip),
        _shape((N_ITEMS, RANK), jnp.float32, one_chip),
        n=16,
    ).compile()
    assert _device_bytes(compiled) < HBM_BYTES


# a batch's packed operand with no lists: the rows' bits, an exclusion
# and an inclusion block and a category block of width 1, three flags
WIDTHS = (1, 1, 1)


def _resident_rows(n_items, n_shards=1):
    """The rows ``ItemRetriever`` keeps resident for ``n_items``: whole
    blocks, and on a mesh whole blocks a shard."""
    return pad_to_multiple(n_items, n_shards * retrieval._ROW_BLOCK)


def _stage1_shapes(replicated, rows, rows_k, n_shards=1):
    n = _resident_rows(N_ITEMS, n_shards)
    return (
        _shape((BATCH, RANK + sum(WIDTHS) + 3), jnp.int32, replicated),
        _shape((n, RANK), jnp.int8, rows_k),
        _shape((n,), jnp.float32, rows),  # per-row scales
        _shape((n,), jnp.float32, rows),  # reciprocal norms
        _shape((n,), jnp.bool_, rows),  # candidacy mask
        _shape((n, 1), jnp.int32, rows_k),  # per-item category codes
    )


def _assert_one_blocked_form(compiled, batch, n_items):
    """The mechanism of PR 36 in the program the chip would run: no op,
    inside a fusion or outside, pads, slices or re-lays an array as
    large as the ``[B, rows]`` scores. What is left is the layout
    ``copy`` of a membership grid as ``pred`` (a byte an element: the
    einsum writes ``[B, rows/2048, 2048]`` batch-major, the scores are
    tiled ``[B, rows]``)."""
    op = re.compile(
        r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]+)\]\S* (pad|slice|copy)\("
    )
    for line in compiled.as_text().splitlines():
        m = op.match(line)
        if m and np.prod([int(d) for d in m[2].split(",")]) >= batch * n_items:
            assert (m[1], m[3]) == ("pred", "copy"), line


def _fusions_of(compiled, shape):
    """How many fusions of a compiled program write a ``pred`` array of
    ``shape``: a membership grid ``[1?, B, rows/2048, 2048]``, or a
    ``[B, rows]`` predicate that the scoring pass reads back."""
    dims = ",".join(str(d) for d in shape)
    return len(re.findall(
        rf"(?m)^\s*(?:ROOT )?%\S+ = pred\[(?:1,)?{dims}\]\S* fusion\(",
        compiled.as_text(),
    ))


def _sorts(compiled):
    """(width along the sorted dimension, stable?) of every ``sort`` and
    every ``TopK`` custom call in a compiled program (an operand's shape
    is read from the line that defines it). A ``TopK`` keeps ties in
    index order on the chip; a ``sort`` does only where it is stable."""
    text = compiled.as_text()
    shapes = {
        m[1]: [int(d) for d in m[2].split(",") if d]
        for m in re.finditer(
            r"(?m)^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]", text)
    }
    found = []
    for line in text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%\S+ = .*? sort\(%([^,)]+).*dimensions=\{(\d+)\}",
            line,
        )
        if m:
            found.append(
                (shapes[m[1]][int(m[2])], "is_stable=true" in line))
        m = re.match(
            r"\s*(?:ROOT )?%\S+ = .*? custom-call\(%([^,)]+)\)"
            r'.*custom_call_target="TopK"', line,
        )
        if m:
            found.append((shapes[m[1]][-1], True))
    return found


def test_int8_stage1_single_device_compiles(one_chip):
    compiled = retrieval._fused_topn_single_2s.lower(
        *_stage1_shapes(one_chip, one_chip, one_chip),
        n=64, shortlist=64, positive_only=False, normalize=False,
        precision="int8", widths=WIDTHS,
    ).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_ecommerce_fused_program_compiles_at_the_taobao_shape(one_chip):
    """The float32 fused retrieval program of the e-commerce cell at its
    widest warm corner: 4,162,024 x 512 resident, a batch of 32,
    exclusion lists of 8,192, a whitelist of 1,024, four category codes,
    per-row cosine flags, all in the batch's one packed operand. It has
    to fit one chip beside the table."""
    n_items, k, b = 4_162_024, 512, 32
    n = _resident_rows(n_items)
    widths = (8192, 1024, 4)
    compiled = retrieval._fused_topn_single.lower(
        _shape((b, k + sum(widths) + 3), jnp.int32, one_chip),
        _shape((n, k), jnp.float32, one_chip),
        _shape((n,), jnp.float32, one_chip),
        _shape((n,), jnp.bool_, one_chip),
        _shape((n, 1), jnp.int32, one_chip),
        n=16, positive_only=True, normalize="rows", widths=widths,
    ).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    _assert_one_blocked_form(compiled, b, n_items)
    # lists of 8,192 and 1,024 against a top-16 keep their grids
    assert _fusions_of(compiled, (b, n // retrieval._LO, retrieval._LO)) == 2


@pytest.mark.parametrize("exclude, include", [(16, 1), (64, 256)])
@pytest.mark.parametrize("batch", [8, 16, 32])
def test_similar_product_two_stage_ladder_compiles_at_the_amazon_shape(
        one_chip, batch, exclude, include):
    """The int8 two-stage program of the Similar Product cell over its
    warm ladder (every batch size at the narrowest and at the widest
    pair of lists: 6 of the 12 executables, 15-20 s of compile each;
    all 12 compiled here when PR 33 was built): 9,400,000 x 512 int8
    resident (4.8 GB; in float32 it would not fit), the device's
    candidate list 64 wide and its stage-1 shortlist 256, cosine
    scores, four category codes, all in the batch's one packed operand.
    Each has to fit one chip beside the table, and no sort or top-k in
    it takes more than 256 + ``exclude`` sub-blocks of 128 scores a
    query: the shortlist's top-k takes its second level (a top-256 of
    262,144 was 1.5 ms of a 10 ms run), over-fetched by the exclusion
    list's width. Every sort in it is stable, so equal scores keep
    their index order (the chip's top-256 of 32,768 did not). The
    exclusion lists go after that top-k: no executable builds their
    membership grid (the whitelist's is the one left), and the category
    compare writes no ``[B, rows]`` predicate of its own."""
    n_items, k = 9_400_000, 512
    n = _resident_rows(n_items)
    widths = (exclude, include, 4)
    compiled = retrieval._fused_topn_single_2s.lower(
        _shape((batch, k + sum(widths) + 3), jnp.int32, one_chip),
        _shape((n, k), jnp.int8, one_chip),
        _shape((n,), jnp.float32, one_chip),  # per-row scales
        _shape((n,), jnp.float32, one_chip),  # reciprocal norms
        _shape((n,), jnp.bool_, one_chip),  # candidacy mask
        _shape((n, 1), jnp.int32, one_chip),  # per-item category codes
        n=64, shortlist=256, positive_only=True, normalize=True,
        precision="int8", widths=widths,
    ).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    _assert_one_blocked_form(compiled, batch, n_items)
    sorts = _sorts(compiled)
    widest = (256 + exclude) * retrieval._SUB
    assert sorts and max(w for w, _ in sorts) <= widest, sorts
    assert all(stable for _, stable in sorts), sorts
    grid = (batch, n // retrieval._LO, retrieval._LO)
    assert _fusions_of(compiled, grid) == (include > 1)
    assert _fusions_of(compiled, (batch, n)) == 0


def test_int8_stage1_shards_over_four_chips(mesh4):
    """The ``shard_map`` stage-1 program ``ItemRetriever`` builds on a
    mesh: each device holds a quarter of the quantized catalog."""
    kernel = functools.partial(
        retrieval._shard_topk_kernel_2s, axis="data", n_local=64,
        shortlist=64, positive_only=False, normalize=False,
        precision="int8", widths=WIDTHS,
    )
    fn = jax.jit(
        jax.shard_map(
            kernel, mesh=mesh4,
            in_specs=(
                P(None, None), P("data", None), P("data"), P("data"),
                P("data"), P("data", None),
            ),
            out_specs=P(None, "data"),
            check_vma=False,
        )
    )
    compiled = fn.lower(
        *_stage1_shapes(
            NamedSharding(mesh4, P()),
            NamedSharding(mesh4, P("data")),
            NamedSharding(mesh4, P("data", None)),
            n_shards=4,
        )
    ).compile()
    catalog_bytes = _resident_rows(N_ITEMS, 4) * (RANK + 4 + 4 + 1)  # rows + scale + rn + mask
    assert compiled.memory_analysis().argument_size_in_bytes < catalog_bytes / 2


@pytest.mark.parametrize("batch, exclude, include", [(8, 16, 1), (32, 64, 256)])
def test_similar_product_float32_shards_over_four_chips_at_the_amazon_shape(
        mesh4, batch, exclude, include):
    """The float32 deployment of the Similar Product catalog on one
    four-chip host: 9,400,000 x 512 row-sharded, the ``shard_map``
    program ``ItemRetriever`` builds on the mesh and the merge after it,
    at the narrowest and the widest corner of the warm ladder. Each chip
    holds a quarter of the table (4.8 GB; the whole, 19.25 GB, fits no
    chip) and the program fits beside it; the merge reads the shards'
    candidates alone."""
    n_items, k, n_local = 9_400_000, 512, 16
    n = _resident_rows(n_items, 4)
    widths = (exclude, include, 4)
    rows = NamedSharding(mesh4, P("data"))
    rows_k = NamedSharding(mesh4, P("data", None))
    rep = NamedSharding(mesh4, P())
    kernel = functools.partial(
        retrieval._shard_topk_kernel, axis="data", n_local=n_local,
        positive_only=True, normalize=True, widths=widths,
    )
    fn = jax.jit(
        jax.shard_map(
            kernel, mesh=mesh4,
            in_specs=(
                P(None, None), P("data", None), P("data"), P("data"),
                P("data", None),
            ),
            out_specs=P(None, "data"),
            check_vma=False,
        )
    )
    compiled = fn.lower(
        _shape((batch, k + sum(widths) + 3), jnp.int32, rep),
        _shape((n, k), jnp.float32, rows_k),
        _shape((n,), jnp.float32, rows),  # reciprocal norms
        _shape((n,), jnp.bool_, rows),  # candidacy mask
        _shape((n, 1), jnp.int32, rows_k),  # one category code an item
    ).compile()
    # a device's arguments: its quarter of the rows, and the per-item
    # vectors of those rows (9 bytes an item)
    shard = n // 4 * k * 4
    args = compiled.memory_analysis().argument_size_in_bytes
    assert shard < args < 1.01 * shard, (args, shard)
    assert _device_bytes(compiled) < HBM_BYTES
    # a list no wider than the shard's top-16 goes after its top-k
    grid = (batch, n // 4 // retrieval._LO, retrieval._LO)
    assert _fusions_of(compiled, grid) == (exclude > n_local) + (include > 1)
    merge = retrieval._merge_candidates.lower(
        _shape((batch, 4 * 2 * n_local), jnp.int32,
               NamedSharding(mesh4, P(None, "data"))),
        n=16, n_local=n_local, rep_s=rep,
    ).compile()
    assert _device_bytes(merge) < 2**20

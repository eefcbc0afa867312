"""Grouped matrix products of sparse experts, as a Pallas TPU kernel.

Rows of `x` sorted by group meet their group's matrix: rows
`[sum(sizes[:g]), sum(sizes[:g+1]))` of x (m, K) times w[g] (K, N). XLA
lowers `jax.lax.ragged_dot` with a row tile of `min(m, 512)` (256 where the
groups are fewer), so every group that has a row multiplies a whole tile of
rows, most of them another group's: at 64 experts and ~8 rows each that is
~60 times the products the rows need, and the call is bound by compute it
then throws away. This kernel walks (row tile, group) VISITS instead: the
grid is (column block, visit, depth block); only the row tiles a group's
rows touch are visited, in order, so an output tile stays in fast memory
while the groups that share it take their turns, and each visit writes the
rows of its own group. A group's matrix block is fetched once for that
group's visits in a row, so a group across two row tiles is read once
where the depth is one block (`tiling` makes it one wherever it fits). The
visits are counted on the device from the group sizes and prefetched into
scalar memory beside the groups' row offsets; the grid is as long as the
count. Rows past the last group are never visited, empty groups are
skipped, and the result's rows outside every group are left as they fall.

`grouped_matmul` is the function; on the TPU it is the kernel, elsewhere
(the CPU of the tests) and where an operand is laid over several devices
(GSPMD cannot partition a Pallas call) `jax.lax.ragged_dot`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:                                    # pltpu only imports on TPU builds
    from jax.experimental.pallas import tpu as pltpu
except Exception:                       # pragma: no cover
    pltpu = None

MIN_ROWS = 16                           # bfloat16 packs 16 rows a tile
# TPU v5e: 197 bf16 TFLOP/s over 819 GB/s. A visit multiplies tm rows by
# its group's K x N block and reads the block: from ~240 rows on the
# product takes longer than the read, so a tile of 128 is the largest that
# keeps a visit bound by its read; a group whose rows fill tiles of 256 is
# bound by its products whatever the tile, and a larger one halves the
# visits
READ_ROWS, COMPUTE_ROWS = 128, 256
WINDOW_BYTES = 12 * 2 ** 20             # a matrix block in fast memory
LANE = 128


def _block(dim: int, most: int) -> int:
    """The largest multiple of 128 that divides `dim` and is at most
    `most`; the whole of `dim` where it is no larger or none does."""
    if dim <= most:
        return dim
    for b in range(most - most % LANE, 0, -LANE):
        if dim % b == 0:
            return b
    return dim


def tiling(m: int, k: int, n: int, groups: int, itemsize: int = 2):
    """(tm, tk, tn) for x (m, k) over `groups` matrices (k, n), from the
    shapes alone (the chip times behind the rule: PERF.md, section 6).

    `tm`: 256 where a group gets 256 rows or more on average (`m /
    groups`: a prompt chunk), else 128, or the least power of two of 16 or
    more that holds all `m` rows. The depth is one block wherever 128 of
    its columns fit `WINDOW_BYTES`, so that a group's block stays in fast
    memory for the group's visits in a row (a group's rows across two row
    tiles are read once) and a visit's rows are fetched once; the column
    block is then as wide as the window holds."""
    if m >= COMPUTE_ROWS * max(groups, 1):
        tm = COMPUTE_ROWS
    else:
        tm = MIN_ROWS
        while tm < min(m, READ_ROWS):
            tm *= 2
    tk = _block(k, max(LANE, WINDOW_BYTES // (LANE * itemsize)))
    tn = _block(n, max(LANE, WINDOW_BYTES // (tk * itemsize)))
    return tm, tk, tn


def _visits(group_sizes, m: int, tm: int):
    """(group of each visit, row tile of each visit, the groups' row
    offsets (G + 1,), visits to make): a group with rows visits every row
    tile from the one its first row lies in to the one its last does; the
    visit arrays are as long as the most visits m rows can need."""
    G, tiles = group_sizes.shape[0], m // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends + tm - 1) // tm - first, 0)
    done = jnp.cumsum(count)            # visits through group g
    i = jnp.arange(tiles + G - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(done[None, :] <= i[:, None], axis=1,
                                dtype=jnp.int32), G - 1)
    tile = jnp.clip(first[group] + i - (done[group] - count[group]), 0,
                    tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    # at least one visit: with no row in any group it writes nothing
    return group, tile, offsets, jnp.maximum(done[-1], 1)


def _kernel(group_ref, tile_ref, offsets_ref, x_ref, w_ref, o_ref, acc_ref,
            *, tm: int, depth: int):
    v, d = pl.program_id(1), pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(d == depth - 1)
    def _store():
        g = group_ref[v]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        # the rows of the groups visited before this one in the same tile
        # are kept; the rest are written by the groups that own them
        o_ref[...] = jnp.where(mine, acc_ref[...],
                               o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)


def _pallas(x, w, group_sizes, interpret=False):
    m, K = x.shape
    G, _, N = w.shape
    item = jnp.dtype(w.dtype).itemsize
    tm, tk, tn = tiling(m, K, N, G, item)
    rows = -(-m // tm) * tm
    xp = jnp.pad(x, ((0, rows - m), (0, 0))) if rows != m else x
    group, tile, offsets, visits = _visits(group_sizes, rows, tm)
    depth = K // tk
    if x.dtype != w.dtype:
        xp = xp.astype(w.dtype)
    fast = (2 * (tm * tk + tk * tn) * item + 2 * tm * tn * x.dtype.itemsize
            + 4 * tm * tn)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, depth=depth),
        out_shape=jax.ShapeDtypeStruct((rows, N), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N // tn, visits, depth),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda n, v, d, grp, tl, off: (tl[v], d)),
                pl.BlockSpec((None, tk, tn),
                             lambda n, v, d, grp, tl, off: (grp[v], d, n))],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, v, d, grp, tl, off: (tl[v], n)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 * 2 ** 20, fast + 8 * 2 ** 20)),
        interpret=interpret, name="expert_gmm",
    )(group, tile, offsets, xp, w)
    return out[:m] if rows != m else out


def _spread(a) -> bool:
    """Whether `a` may be laid over more than one device, as far as the
    code can see: a placed array by its sharding; a traced one by its
    type's, which names the axes it is split over where the mesh's axes
    are explicit and hides them where they are automatic (under a mesh of
    more than one device set around the trace, so then: may be). Outside
    any such mesh a trace shows nothing of a jit's `in_shardings`."""
    sharding = getattr(a, "sharding", None)
    if sharding is not None:
        return (len(sharding.device_set) > 1
                and not sharding.is_fully_replicated)
    sharding = jax.typeof(a).sharding
    mesh = sharding.mesh
    if mesh.empty or mesh.size == 1:
        return False
    if all(t == jax.sharding.AxisType.Explicit for t in mesh.axis_types):
        return any(s is not None for s in sharding.spec)
    return True


def grouped_matmul(x, w, group_sizes, *, interpret: bool = False):
    """x (m, K) rows sorted by group, w (G, K, N), group_sizes (G,) int32
    with sum <= m -> (m, N) in x's dtype, accumulated in float32. Rows past
    the last group come out as they may. `interpret` runs the kernel in
    the Pallas interpreter (tests)."""
    if interpret:
        return _pallas(x, w, group_sizes, interpret=True)
    if _spread(x) or _spread(w):
        return jax.lax.ragged_dot(x, w, group_sizes)
    return jax.lax.platform_dependent(x, w, group_sizes, tpu=_pallas,
                                      default=jax.lax.ragged_dot)

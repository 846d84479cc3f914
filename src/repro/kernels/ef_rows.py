"""Row copies between the EF store and the cohort's rows (DESIGN.md §14).

The cohort engine's error-feedback store is an (I, P) float32 array in HBM,
one row per client, and each round reads and writes the S rows of its
cohort. On the TPU the array is laid out in (8, 128) tiles: a row is spread
over ceil(P / 128) tiles, and the eight rows of one tile group (rows
8b .. 8b + 7) share them. XLA's generic gather copies most of the store
before it picks rows, and its scatter runs one bounds-checked update per
row. These kernels move the cohort's tile groups and no other byte of the
store:

* ``ef_rows_gather``: row ``ids[s]`` of the store -> row ``s`` of an
  (S, P) output. Each touched tile group is copied HBM -> VMEM once, its
  cohort rows are picked into an (S, width) block, and the block is
  copied to the output.
* ``ef_rows_scatter``: row ``s`` of the rows -> row ``ids[s]`` of the store,
  which is aliased to the output, so the store is written in place. The
  rows come into VMEM as an (S, width) block; each touched tile group is
  read once, its cohort rows replaced, and written back.

The TPU's DMAs move whole tiles (a one-row slice of a tiled HBM array is
refused), so an access moves the touched tile groups, up to 8 · S · P · 4
bytes, and not S · P · 4 of rows. The ids are visited sorted (``_plan``),
so each touched group is one run of consecutive ids: it is read and
written once, and up to ``IN_FLIGHT`` groups are in flight with no two on
the same bytes. Columns go in chunks of ``width`` (a multiple of 128 that
keeps the (S, width) block within ``BLOCK_BYTES``; the last chunk is
shifted left to end at the padded width, so every chunk has one static
width and a few columns are copied twice, with the same values).

The DMAs may reach past the store's logical edge into its tile padding
(the last group when I is not a multiple of 8, the last lanes when P is
not a multiple of 128): on the TPU that padding is part of the buffer. In
interpret mode it is not, so there the wrappers pad the arrays to whole
tiles first and crop the result.

Both kernels rely on the ids being distinct and in range, which
``fed.cohort_sample`` guarantees (a draw without replacement from [0, I)):
a repeated id would make the scatter's result depend on the order of its
writes, and an id out of range reads or writes outside the store.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES, LANES = 8, 128         # the (8, 128) tile of a 32-bit array
BLOCK_BYTES = 8 << 20            # the (S, width) block of cohort rows in VMEM
IN_FLIGHT = 8                    # tile-group reads in flight


def _chunking(s: int, p: int) -> tuple[int, int]:
    """(number of column chunks, chunk width): the widest multiple of 128
    whose (S, width) float32 block fits BLOCK_BYTES, spread evenly over
    the lane-padded width."""
    tiles = pl.cdiv(p, LANES)
    most = max(1, BLOCK_BYTES // (4 * LANES * SUBLANES * pl.cdiv(s, SUBLANES)))
    n = pl.cdiv(tiles, most)
    return n, LANES * pl.cdiv(tiles, n)


def _plan(ids):
    """The ids sorted, where each sorted id came from, and the run (one
    per touched tile group) each sorted id belongs to: three (S,) int32
    arrays for scalar prefetch."""
    ids = ids.astype(jnp.int32)
    order = jnp.argsort(ids).astype(jnp.int32)
    sid = ids[order]
    group = sid // SUBLANES
    run = jnp.cumsum(jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), (group[1:] != group[:-1]).astype(
            jnp.int32)]))
    return sid, order, run


def _chunk_cols(width: int, padded: int):
    """This grid step's columns: chunk j, the last one shifted left to end
    at the padded width."""
    start = jnp.minimum(pl.program_id(0) * width, padded - width)
    return pl.ds(pl.multiple_of(start, LANES), width)


class _Runs:
    """Scalar helpers over the sorted plan in SMEM: run k's group, and
    whether sorted id k opens or closes its run."""

    def __init__(self, sid, run):
        self.sid, self.run, self.n = sid, run, sid.shape[0]

    def group(self, k):
        g = (self.sid[k] // SUBLANES) * SUBLANES
        return pl.ds(pl.multiple_of(g, SUBLANES), SUBLANES)

    def slot(self, k):
        return self.run[k] % IN_FLIGHT

    def opens(self, k):
        return (k == 0) | (self.run[k] != self.run[jnp.maximum(k - 1, 0)])

    def closes(self, k):
        return (k == self.n - 1) | (
            self.run[k] != self.run[jnp.minimum(k + 1, self.n - 1)])


def _gather_kernel(sid_ref, src_ref, run_ref, store_ref, out_ref, buf, rows,
                   rsem, osem, *, width: int, padded: int):
    runs = _Runs(sid_ref, run_ref)
    cols = _chunk_cols(width, padded)

    def read(k):
        return pltpu.make_async_copy(store_ref.at[runs.group(k), cols],
                                     buf.at[runs.slot(k)],
                                     rsem.at[runs.slot(k)])

    def body(k, c):
        # the group of the run opening IN_FLIGHT - 1 ids ahead; its slot's
        # last run ended before id k, so its rows are already picked
        ahead = k + IN_FLIGHT - 1
        ahead_c = jnp.minimum(ahead, runs.n - 1)

        @pl.when((ahead < runs.n) & runs.opens(ahead_c))
        def _():
            read(ahead_c).start()

        @pl.when(runs.opens(k))
        def _():
            read(k).wait()

        r = sid_ref[k] % SUBLANES
        rows[pl.ds(src_ref[k], 1), :] = buf[runs.slot(k), pl.ds(r, 1), :]
        return c

    for k in range(min(IN_FLIGHT - 1, runs.n)):
        pl.when(runs.opens(k))(lambda k=k: read(k).start())
    jax.lax.fori_loop(0, runs.n, body, 0)
    # S rows rounded up to whole tile groups (into the padding): an
    # aligned DMA
    out = pltpu.make_async_copy(rows, out_ref.at[pl.ds(0, rows.shape[0]),
                                                 cols], osem.at[0])
    out.start()
    out.wait()


def _scatter_kernel(sid_ref, src_ref, run_ref, rows_ref, store_ref, out_ref,
                    buf, rows, rsem, wsem, osem, *, width: int, padded: int):
    del store_ref                   # aliased to out_ref
    runs = _Runs(sid_ref, run_ref)
    cols = _chunk_cols(width, padded)

    def read(k):
        return pltpu.make_async_copy(out_ref.at[runs.group(k), cols],
                                     buf.at[runs.slot(k)],
                                     rsem.at[runs.slot(k)])

    def write(k):
        return pltpu.make_async_copy(buf.at[runs.slot(k)],
                                     out_ref.at[runs.group(k), cols],
                                     wsem.at[runs.slot(k)])

    def wait_write(run):
        # a wait needs only the slot and the size
        pltpu.make_async_copy(buf.at[run % IN_FLIGHT],
                              out_ref.at[pl.ds(0, SUBLANES), cols],
                              wsem.at[run % IN_FLIGHT]).wait()

    load = pltpu.make_async_copy(rows_ref.at[pl.ds(0, rows.shape[0]), cols],
                                 rows, osem.at[0])
    load.start()
    for k in range(min(IN_FLIGHT - 1, runs.n)):
        pl.when(runs.opens(k))(lambda k=k: read(k).start())
    load.wait()

    def body(k, c):
        # the run opening IN_FLIGHT - 1 ids ahead reuses the slot of the run
        # IN_FLIGHT before it, which ended (and started its write) before
        # id k; each group is one run, so no read waits on another's write
        ahead = k + IN_FLIGHT - 1
        ahead_c = jnp.minimum(ahead, runs.n - 1)

        @pl.when((ahead < runs.n) & runs.opens(ahead_c))
        def _():
            @pl.when(run_ref[ahead_c] >= IN_FLIGHT)
            def _():
                wait_write(run_ref[ahead_c] - IN_FLIGHT)

            read(ahead_c).start()

        @pl.when(runs.opens(k))
        def _():
            read(k).wait()

        r = sid_ref[k] % SUBLANES
        buf[runs.slot(k), pl.ds(r, 1), :] = rows[pl.ds(src_ref[k], 1), :]

        @pl.when(runs.closes(k))
        def _():
            write(k).start()

        return c

    jax.lax.fori_loop(0, runs.n, body, 0)
    # the last IN_FLIGHT runs' writes were never waited on
    last = run_ref[runs.n - 1]
    for d in range(IN_FLIGHT):
        pl.when(last - d >= 0)(lambda d=d: wait_write(last - d))


def _tile_pad(x, interpret):
    """Interpret mode: ``x`` padded to whole (8, 128) tiles, as the TPU
    lays it out. On the TPU: ``x`` itself."""
    if not interpret:
        return x
    r, c = x.shape
    return jnp.pad(x, ((0, -r % SUBLANES), (0, -c % LANES)))


def _call(kernel, ids, arrays, out_rows, p, dtype, *, sems, interpret,
          **kw):
    """One grid step per column chunk. Scratch: IN_FLIGHT (8, width) group
    buffers, the (S, width) block of cohort rows, IN_FLIGHT DMA semaphores
    for each of ``sems`` - 1 group streams and one for the block."""
    s = ids.shape[0]
    n, width = _chunking(s, p)
    padded = LANES * pl.cdiv(p, LANES)
    if interpret:
        out_rows, p = SUBLANES * pl.cdiv(out_rows, SUBLANES), padded
    s8 = SUBLANES * pl.cdiv(s, SUBLANES)
    vmem = 4 * width * (IN_FLIGHT * SUBLANES + s8)
    return pl.pallas_call(
        functools.partial(kernel, width=width, padded=padded),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(arrays),
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((IN_FLIGHT, SUBLANES, width), dtype),
                            pltpu.VMEM((s8, width), dtype)]
            + [pltpu.SemaphoreType.DMA((IN_FLIGHT,))] * (sems - 1)
            + [pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((out_rows, p), dtype),
        # the scratch plus room for the compiler's own
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem + (16 << 20)),
        interpret=interpret, **kw,
    )(*_plan(ids), *[_tile_pad(a, interpret) for a in arrays])


def ef_rows_gather(store, ids, *, interpret=False):
    """(I, P) float32 store, (S,) distinct in-range ids -> the (S, P) rows
    ``store[ids]``, bit for bit. ``interpret``: as ``pallas_call``'s."""
    s, p = ids.shape[0], store.shape[1]
    out = _call(_gather_kernel, ids, [store], s, p, store.dtype, sems=2,
                name="ef_rows_gather", interpret=interpret)
    return out[:s, :p] if interpret else out


def ef_rows_scatter(store, ids, rows, *, interpret=False):
    """The store with row ``ids[s]`` replaced by ``rows[s]`` for each s,
    written in place (the store's buffer is the output's); every other row
    keeps its bits. ``ids`` distinct and in range."""
    i, p = store.shape
    out = _call(_scatter_kernel, ids, [rows.astype(store.dtype), store], i,
                p, store.dtype, sems=3, input_output_aliases={4: 0},
                name="ef_rows_scatter", interpret=interpret)
    return out[:i, :p] if interpret else out

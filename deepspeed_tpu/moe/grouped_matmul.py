"""Grouped matmul over rows sorted by expert: the dropless layer's kernel.

``lhs`` is (M, K): M rows in tiles of ``tile_m``, every row of tile ``t``
belonging to expert ``tile_expert[t]``; ``rhs`` is (E, K, N), one matrix an
expert.  ``out[t] = lhs[t] @ rhs[tile_expert[t]]`` for the first
``n_tiles`` tiles and is NOT WRITTEN for the others (the caller never reads
them): the grid is static, the work is not.  A tile past the last active
one maps every block to the last active step's, so it moves nothing and
computes nothing.

The contraction is whole in one block (K x tile_n of an expert's matrix at
a time, at most 4 MB), so there is no accumulator to carry and each
expert's matrix is read once per tile of its rows: with some tens of rows
an expert the kernel is bound by those bytes, not by the MXU.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import \
    _interpret_default

KERNEL_NAME = "moe_grouped_matmul"
_RHS_BLOCK_BYTES = 4 << 20


def _fit_tile_n(K, N, itemsize):
    """Widest lane-aligned divisor of N whose K x tile_n block stays within
    ``_RHS_BLOCK_BYTES`` (N itself when it is small or has none)."""
    tile = N
    while tile * K * itemsize > _RHS_BLOCK_BYTES and tile % 256 == 0:
        tile //= 2
    return tile


def _kernel(tile_expert_ref, n_tiles_ref, lhs_ref, rhs_ref, out_ref):
    del tile_expert_ref         # read by the index maps only

    @pl.when(pl.program_id(0) < n_tiles_ref[0])
    def _():
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret", "name"))
def grouped_matmul(lhs, rhs, tile_expert, n_tiles, *, tile_m,
                   interpret=None, name=KERNEL_NAME):
    """See the module docstring.  ``tile_expert``: (M // tile_m,) int32;
    ``n_tiles``: () or (1,) int32.  ``name``: what the kernel is called in
    the compiled program and the device trace (a caller with two kinds of
    call, prefill and decode, tells them apart there)."""
    M, K = lhs.shape
    E, K2, N = rhs.shape
    assert K == K2 and M % tile_m == 0, (lhs.shape, rhs.shape, tile_m)
    if interpret is None:
        interpret = _interpret_default()
    tile_n = _fit_tile_n(K, N, rhs.dtype.itemsize)
    n_j = N // tile_n

    def where(i, j, n):
        """(row tile, column tile) that grid step (i, j) works on: its own
        while ``i`` is active, else the last active step's."""
        last = jnp.maximum(n[0] - 1, 0)
        active = i < n[0]
        return jnp.where(active, i, last), jnp.where(active, j, n_j - 1)

    def lhs_map(i, j, te, n):
        return where(i, j, n)[0], 0

    def rhs_map(i, j, te, n):
        t, jj = where(i, j, n)
        return te[t], 0, jj

    def out_map(i, j, te, n):
        return where(i, j, n)

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(M // tile_m, n_j),
            in_specs=[pl.BlockSpec((tile_m, K), lhs_map),
                      pl.BlockSpec((1, K, tile_n), rhs_map)],
            out_specs=pl.BlockSpec((tile_m, tile_n), out_map)),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name=name,
    )(tile_expert.astype(jnp.int32), n_tiles.reshape(1).astype(jnp.int32),
      lhs, rhs)

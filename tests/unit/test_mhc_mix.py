"""The two kernels of the residual mixes (``ops/transformer/mhc_mix.py``),
interpreted on the CPU at toy widths, against the equations as the
benchmark's reference keeps them (``benchmark/architectures/motif.py``
``_mhc_pre`` / ``_mhc_post``, which imports nothing of the program).

Tolerances, and why: both sides compute in float32, so H_post and H_res
agree to a few of float32's roundings (2e-6 on values of at most 2) and
``u`` and the new streams to 2e-5 on values of a few units; a bf16 call
rounds only what it WRITES (``u``, the streams), so those are held to half
a bf16 spacing of the reference's value plus the same 2e-5.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import mhc_mix

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import cells          # noqa: E402

N_STREAMS, WIDTH, EPS, CLAMP = 4, 32, 1e-5, 1e6


@pytest.fixture(scope="module")
def arch():
    return cells.load_module(os.path.join(
        BENCH_DIR, "architectures", "motif.py"), "bench_arch_motif_mix")


def _draw(rows, dtype, seed=0):
    """Streams of a few units, a norm near one, scores with sigma ~1."""
    n, E = N_STREAMS, WIDTH
    rng = np.random.default_rng(seed)
    mix = {"norm": 1 + 0.1 * rng.standard_normal(n * E),
           "phi": 0.08 * rng.standard_normal((n * E, 2 * n + n * n)),
           "alpha": np.asarray([0.7, 0.9, 1.1]),
           "beta": 0.3 * rng.standard_normal(2 * n + n * n)}
    mix = {k: jnp.asarray(v, dtype) for k, v in mix.items()}
    X = jnp.asarray(1.3 * rng.standard_normal((rows, n * E)), dtype)
    y = jnp.asarray(rng.standard_normal((rows, E)), dtype)
    return mix, X, y


def _kernels(mix, X, y, iters):
    folded, consts = mhc_mix.fold_phi(mix["norm"], mix["phi"], mix["alpha"],
                                      mix["beta"], N_STREAMS)
    u, h_post, h_res, err = mhc_mix.mhc_pre_mix(
        X, folded, consts, n=N_STREAMS, iters=iters, eps=EPS)
    out = mhc_mix.mhc_post_mix(X, y, h_post, h_res, clamp=CLAMP)
    return u, h_post, h_res, err, out


def _close(got, want, dtype):
    """``got`` (written in ``dtype``) against the f32 ``want``."""
    want = np.asarray(want, np.float32)
    spacing = 2.0 ** -8 if dtype == jnp.bfloat16 else 0.0
    np.testing.assert_array_less(
        np.abs(np.asarray(got, np.float32) - want),
        2e-5 + spacing * np.abs(want))


@pytest.mark.parametrize("iters", [20, 2])
@pytest.mark.parametrize("rows", [40, 48, 300])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_both_kernels_are_the_equations(arch, dtype, rows, iters):
    """40 rows: no multiple of a bf16 tile's sixteen; 48: a decode
    program's; 300: two blocks of 256, the second ragged.  What the last
    block holds past the rows reaches neither the error nor the output:
    the same rows give the same bits with NaN rows behind them."""
    n, E = N_STREAMS, WIDTH
    mix, X, y = _draw(rows, dtype, seed=rows + iters)
    u, h_post, h_res, err, out = _kernels(mix, X, y, iters)
    assert u.dtype == dtype and out.dtype == dtype \
        and h_res.dtype == jnp.float32

    c = {"rms_norm_eps": EPS, "mhc_sinkhorn_iters": iters,
         "hidden_clamp": CLAMP}
    X32 = X.astype(jnp.float32).reshape(rows, n, E)
    with jax.default_matmul_precision("highest"):
        u_ref, post_ref, res_ref = arch._mhc_pre(
            X32, mix, c, arch._matmul(arch._kept(None)))
        out_ref = arch._mhc_post(X32, y.astype(jnp.float32), post_ref,
                                 res_ref, c)
    np.testing.assert_allclose(np.asarray(h_post), np.asarray(post_ref),
                               rtol=0, atol=2e-6)
    np.testing.assert_allclose(np.asarray(h_res).reshape(rows, n, n),
                               np.asarray(res_ref), rtol=0, atol=2e-6)
    _close(u, u_ref, dtype)
    _close(out, np.asarray(out_ref).reshape(rows, n * E), dtype)
    # the error is what is left in the reference's H_res too
    res = np.asarray(res_ref)
    left = np.maximum(np.abs(res.sum(2) - 1).max(1),
                      np.abs(res.sum(1) - 1).max(1))
    np.testing.assert_allclose(np.asarray(err), left, rtol=0, atol=2e-6)
    assert bool(left.max() < 1e-3) is (iters == 20)

    behind = 512 - rows
    nan = lambda a: jnp.concatenate(                   # noqa: E731
        [a, jnp.full((behind,) + a.shape[1:], jnp.nan, a.dtype)])
    padded = _kernels(mix, nan(X), nan(y), iters)
    for alone, among in zip((u, h_post, h_res, err, out), padded):
        np.testing.assert_array_equal(np.asarray(alone, np.float32),
                                      np.asarray(among[:rows], np.float32))


def test_three_bf16_pieces_of_phi_are_the_six_passes_on_bf16_streams():
    """The folded matrix's three pieces sum to ``norm * phi`` bit for bit,
    and ONE bf16 pass over them with float32 accumulation is as near the
    float64 product as ``Precision.HIGHEST`` on the float32 values is:
    float32's rounding of a sum of 128 terms, nothing of bf16's."""
    n, E = N_STREAMS, WIDTH
    mix, X, _ = _draw(300, jnp.float32, seed=5)
    X = X.astype(jnp.bfloat16)
    folded, _ = mhc_mix.fold_phi(mix["norm"], mix["phi"], mix["alpha"],
                                 mix["beta"], n)
    G = 2 * n + n * n
    full = mix["norm"].astype(jnp.float32)[:, None] * mix["phi"]
    folded = folded.T                   # held transposed: (128, n E)
    pieces = [np.asarray(folded[:, p * G:(p + 1) * G], np.float32)
              for p in range(3)]
    np.testing.assert_array_equal(pieces[0] + pieces[1] + pieces[2],
                                  np.asarray(full))
    assert not np.asarray(folded[:, 3 * G:], np.float32).any()

    one_pass = jnp.dot(X, folded, preferred_element_type=jnp.float32)
    one_pass = np.asarray(one_pass[:, :G] + one_pass[:, G:2 * G]
                          + one_pass[:, 2 * G:3 * G])
    highest = np.asarray(jnp.dot(X.astype(jnp.float32), full,
                                 precision=jax.lax.Precision.HIGHEST))
    exact = np.asarray(X, np.float64) @ np.asarray(full, np.float64)
    scale = np.abs(exact).max()
    assert np.abs(highest - exact).max() < 4e-7 * scale
    assert np.abs(one_pass - exact).max() < 4e-7 * scale
    # bf16's own rounding of phi would be four thousand times that
    lossy = np.asarray(X, np.float64) @ pieces[0].astype(np.float64)
    assert np.abs(lossy - exact).max() > 1e-4 * scale


@pytest.mark.parametrize("rows,size,pre,post", [
    (2048, 2, 256, 128),    # a chunk of the cell: 8 and 16 blocks
    (48, 2, 48, 48),        # a decode program: one block
    (40, 2, 48, 48),        # whole bf16 tiles of 16 rows
    (40, 4, 40, 40),        # whole f32 tiles of 8
    (128, 2, 128, 128),
    (300, 2, 256, 128),     # ragged last block
    (2048, 4, 128, 128),    # f32 streams: half the rows in the same bytes
])
def test_row_block_follows_rows_width_and_dtype(rows, size, pre, post):
    """The blocks at the published widths (4 x 4096): what the two
    wrappers ask ``_fit_rows`` for."""
    nE, E, sublanes = 4 * 4096, 4096, 32 // size
    assert mhc_mix._fit_rows(rows, (nE + E) * size + 512, sublanes) == pre
    assert mhc_mix._fit_rows(rows, 2 * nE * size + E * size + 1024,
                             sublanes) == post

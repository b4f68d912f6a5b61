"""Per-kernel correctness: shape/dtype sweeps against the ref.py oracles,
all in interpret mode (the kernel body executes in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.blockdct.ops import blockdct_quantize
from repro.kernels.blockdct.ref import blockdct_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.qtransfer.ops import qtransfer
from repro.kernels.qtransfer.ref import qtransfer_ref
from repro.kernels.tile_shift import tile_shift


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("B,H,Hk,Sq,Sk,D,causal,window,dtype", [
    (2, 4, 2, 128, 128, 64, True, None, jnp.float32),
    (1, 4, 4, 256, 256, 64, False, None, jnp.float32),
    (1, 8, 2, 256, 256, 128, True, 96, jnp.float32),
    (2, 2, 1, 64, 192, 64, True, None, jnp.float32),   # cross Sq != Sk
    (1, 4, 2, 128, 128, 64, True, None, jnp.bfloat16),
])
def test_flash_attention_matches_ref(B, H, Hk, Sq, Sk, D, causal, window,
                                     dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hk, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hk, D), dtype)
    o = flash_attention(q, k, v, causal=causal, window=window,
                        q_blk=64, k_blk=64, interpret=True)
    r = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), causal=causal,
                      window=window).transpose(0, 2, 1, 3)
    tol = 0.03 if dtype == jnp.bfloat16 else 0.02
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=tol)


def test_flash_attention_block_shape_independence():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.float32)
    o1 = flash_attention(q, k, v, q_blk=32, k_blk=64, interpret=True)
    o2 = flash_attention(q, k, v, q_blk=128, k_blk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-3)


# ------------------------------------------------------------- qtransfer
@pytest.mark.parametrize("H,W,radius", [(64, 96, 8), (64, 96, 16),
                                        (128, 128, 16), (48, 160, 8)])
def test_qtransfer_matches_ref(H, W, radius):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    anchor = jax.random.uniform(ks[0], (H, W), jnp.float32) * 255
    mv = jax.random.randint(ks[1], (H // 16, W // 16, 2), -radius,
                            radius + 1, jnp.int32)
    resid = jax.random.normal(ks[2], (H, W), jnp.float32) * 8
    o = qtransfer(anchor, mv, resid, radius=radius, interpret=True)
    r = qtransfer_ref(anchor, mv, resid, radius=radius)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-4)


@settings(deadline=None, max_examples=10)
@given(dy=st.integers(-16, 16), dx=st.integers(-16, 16))
def test_qtransfer_uniform_shift_property(dy, dx):
    """A uniform MV field equals a (clamped) whole-frame shift."""
    H, W = 48, 64
    anchor = jnp.arange(H * W, dtype=jnp.float32).reshape(H, W) % 251
    mv = jnp.full((H // 16, W // 16, 2), 0, jnp.int32
                  ).at[..., 0].set(dy).at[..., 1].set(dx)
    resid = jnp.zeros((H, W), jnp.float32)
    o = np.asarray(qtransfer(anchor, mv, resid, radius=16, interpret=True))
    r = np.asarray(qtransfer_ref(anchor, mv, resid, radius=16))
    np.testing.assert_allclose(o, r, atol=1e-4)


def test_qtransfer_batched():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    anchor = jax.random.uniform(ks[0], (3, 32, 32), jnp.float32) * 255
    mv = jax.random.randint(ks[1], (3, 2, 2, 2), -4, 5, jnp.int32)
    resid = jnp.zeros((3, 32, 32), jnp.float32)
    o = qtransfer(anchor, mv, resid, interpret=True)
    assert o.shape == (3, 32, 32)
    assert not np.any(np.isnan(np.asarray(o)))


# --------------------------------------------------------------- blockdct
@pytest.mark.parametrize("nb,tile,quality", [
    (64, 32, 50.0), (100, 32, 20.0), (256, 256, 80.0), (7, 8, 95.0),
])
def test_blockdct_matches_ref(nb, tile, quality):
    blocks = jax.random.uniform(jax.random.PRNGKey(4), (nb, 8, 8),
                                jnp.float32) * 255 - 128
    q, rec = blockdct_quantize(blocks, quality, tile=tile, interpret=True)
    qr, recr = blockdct_ref(blocks, quality)
    # round() at the exact .5 boundary may differ by 1 ulp of quantization
    assert float(jnp.max(jnp.abs(q - qr))) <= 1.0
    assert float(jnp.mean(jnp.abs(q - qr))) < 0.01
    np.testing.assert_allclose(np.asarray(rec), np.asarray(recr), atol=1.0)


def test_blockdct_energy_decreases_with_quality():
    blocks = jax.random.uniform(jax.random.PRNGKey(5), (32, 8, 8),
                                jnp.float32) * 255 - 128
    nz = []
    for q in (10.0, 50.0, 90.0):
        qq, _ = blockdct_quantize(blocks, q, interpret=True)
        nz.append(int((jnp.abs(qq) > 0).sum()))
    assert nz[0] <= nz[1] <= nz[2]


# ---------------------------------------------------------- tile shift
@pytest.mark.parametrize("n", [12, 24, 520])
def test_tile_shift_matches_jnp_selects(n):
    """The Pallas lane-gather kernel cuts the same 16x16 block out of each
    32x32 square of four tiles as the plain-jnp selects, bit for bit,
    for every (fy, fx) and a block count that fills no whole step."""
    from repro.codec.motion import _shift_cols, _shift_rows
    rng = np.random.default_rng(n)
    tl, tr, bl, br = (jnp.asarray(rng.uniform(0, 255, (n, 256))
                                  .astype(np.float32)) for _ in range(4))
    f = np.arange(n) % 256                 # every (fy, fx) once n >= 256
    fy, fx = jnp.asarray(f // 16, jnp.int32), jnp.asarray(f % 16, jnp.int32)
    want = _shift_cols(_shift_rows(tl, bl, fy), _shift_rows(tr, br, fy), fx)
    got = jax.jit(lambda *a: tile_shift(*a, interpret=True))(
        tl, tr, bl, br, fy, fx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

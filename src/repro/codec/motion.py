"""Block motion estimation (16×16 macroblocks, ±R integer pel).

Two search STRATEGIES, each with a fallback and a Pallas-kernel path:

``search="exhaustive"`` (default) — full ±R search over all (2R+1)²
candidates, identical semantics across three implementations (dy-major
candidate order, first-wins tie-breaking):

* ``block_sad_scan`` — the legacy oracle: a ``lax.scan`` over candidate
  offsets, each step materializing a whole-frame shifted copy of the
  padded reference.  Correct but HBM-bound: (2R+1)² full-frame slices.
* ``block_sad`` — the vmapped per-macroblock form the fused decode path
  uses: each macroblock gathers its (MB+2R)² search window once, then the
  candidate loop slices inside those resident windows — no whole-frame
  copies, flat memory in the radius.
* ``block_sad(use_kernel=True)`` — the Pallas TPU kernel in
  ``repro.kernels.motion_sad`` (multiple macroblock rows per grid step,
  the ±R halo as extra row blocks, candidate windows read by rotating
  the band one dy row and one dx lane at a time).

``search="diamond"`` — traced coarse-to-fine (three-step / diamond)
search: a STATIC step schedule (``diamond_steps``: largest power of two
≤ R, halving to 1) probes the 3×3 neighbourhood of each macroblock's
running best offset, clipped to ±R.  Evaluates 1 + 9·len(steps)
candidates instead of (2R+1)² (37 vs 289 at R=8 — under ¼), all shapes
static so the trace is jit-stable.  The found SAD is ≥ the exhaustive
SAD by construction (the probe set is a subset of the exhaustive
candidate set, and per-candidate SADs are computed identically); quality
vs exhaustive is a documented tolerance contract (docs/fused_encoder.md),
not bit-exactness.  ``block_sad_diamond`` is the pure-jnp form;
``block_sad(search="diamond", use_kernel=True)`` routes to the Pallas
diamond kernel (bit-exact MVs vs the fallback).

``dtype=jnp.bfloat16`` selects the bf16 storage variant (inputs cast to
bf16, SADs accumulated in f32) on both the fallbacks and the kernels.

The warp (motion compensation) is the same block-gather primitive the
hybrid decoder's quality transfer uses; on a TPU its sub-tile shift is
the Pallas kernel ``repro.kernels.tile_shift``.  The fused warp +
residual kernel ``repro.kernels.qtransfer`` clips the motion to ±16, so
it equals ``warp_blocks`` only within that bound.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

f32 = jnp.float32
MB = 16  # macroblock size
# below this many macroblocks the diamond KERNEL loses to the traced
# descent (per-probe dispatch overhead is amortized over too few
# blocks).  720p is 3600 blocks, the 64x96 bench canvas is 24.
_DIAMOND_KERNEL_MIN_BLOCKS = 256


def diamond_kernel_profitable(H: int, W: int) -> bool:
    """Static dispatch predicate for ``block_sad(use_kernel=True,
    search="diamond")``: route to the Pallas kernel only where it can
    win.  Two static facts decide it — the macroblock count (small
    canvases can't amortize the per-probe kernel dispatch: 0.82x vs the
    traced descent at 64x96) and the backend (in interpret mode the
    kernel's probe loop runs as host Python per grid step, which loses to
    the traced descent at EVERY shape — measured ~0.8x even at 720p).
    Both are known at trace time, so the dispatch never retraces."""
    if (H // MB) * (W // MB) < _DIAMOND_KERNEL_MIN_BLOCKS:
        return False
    from repro.kernels.motion_sad.ops import on_tpu
    return on_tpu()


def _offsets(radius: int):
    r = jnp.arange(-radius, radius + 1)
    dy, dx = jnp.meshgrid(r, r, indexing="ij")
    return jnp.stack([dy.reshape(-1), dx.reshape(-1)], axis=1)  # (K, 2)


def block_sad_scan(cur, ref, radius: int = 8):
    """Legacy scan-over-candidates full search — the bit-exactness oracle.

    cur/ref: (H, W) with H, W multiples of 16.  One whole-frame shifted
    SAD per candidate offset; kept only as the reference implementation
    for the vmapped fallback and the Pallas kernel.
    """
    H, W = cur.shape
    nby, nbx = H // MB, W // MB
    pad = radius
    refp = jnp.pad(ref.astype(f32), pad, mode="edge")
    cur = cur.astype(f32)
    offs = _offsets(radius)

    def step(carry, off):
        best_sad, best_idx, idx = carry
        dy, dx = off[0], off[1]
        shifted = lax.dynamic_slice(refp, (pad + dy, pad + dx), (H, W))
        diff = jnp.abs(cur - shifted)
        sad = diff.reshape(nby, MB, nbx, MB).sum(axis=(1, 3))
        better = sad < best_sad
        best_sad = jnp.where(better, sad, best_sad)
        best_idx = jnp.where(better, idx, best_idx)
        return (best_sad, best_idx, idx + 1), None

    init = (jnp.full((nby, nbx), jnp.inf, f32),
            jnp.zeros((nby, nbx), jnp.int32), jnp.int32(0))
    (best_sad, best_idx, _), _ = lax.scan(step, init, offs)
    mv = offs[best_idx]  # (nby, nbx, 2)
    return mv.astype(jnp.int32), best_sad


def diamond_steps(radius: int) -> tuple:
    """Static step schedule of the coarse-to-fine search: the largest
    power of two ≤ radius, halving down to 1.  Shared by the pure-jnp
    fallback and the Pallas diamond kernel so probe order (and therefore
    tie-breaking) is identical everywhere."""
    s = 1
    while s * 2 <= radius:
        s *= 2
    steps = []
    while s >= 1:
        steps.append(s)
        s //= 2
    return tuple(steps)


def diamond_num_evals(radius: int) -> int:
    """Candidate evaluations the diamond search performs per macroblock
    (center + 9 probes per step) — 37 at R=8 vs (2R+1)² = 289 exhaustive."""
    return 1 + 9 * len(diamond_steps(radius))


def _search_prelude(cur, ref, radius: int, dtype):
    """Shared head of the fallback searches: per-macroblock current
    blocks (f32) and the per-block (MB+2R)² resident search windows."""
    store = dtype or f32
    H, W = cur.shape
    nby, nbx = H // MB, W // MB
    win = MB + 2 * radius
    refp = jnp.pad(ref.astype(store), radius, mode="edge")
    # (nby, nbx, MB, MB) current blocks, f32 accumulation
    curb = cur.astype(store).astype(f32).reshape(
        nby, MB, nbx, MB).transpose(0, 2, 1, 3)
    # (nby, nbx, MB+2R, MB+2R) per-block search windows — gathered ONCE
    by = jnp.arange(nby) * MB
    bx = jnp.arange(nbx) * MB
    wins = jax.vmap(lambda y0: jax.vmap(
        lambda x0: lax.dynamic_slice(refp, (y0, x0), (win, win)))(bx))(by)
    return curb, wins.astype(f32), nby, nbx


def block_sad_diamond(cur, ref, radius: int = 8, *, dtype=None):
    """Traced coarse-to-fine search (pure-jnp form): (mv, sad) like
    ``block_sad`` but evaluating only ``diamond_num_evals(radius)``
    candidates per macroblock.  Every probe's SAD is computed by the SAME
    slice-and-reduce expression the exhaustive fallback uses, so
    SAD(diamond) ≥ SAD(exhaustive) holds exactly (subset of the candidate
    set), and equals it whenever the greedy descent finds the global
    minimum (smooth / translational content)."""
    curb, wins, nby, nbx = _search_prelude(cur, ref, radius, dtype)

    def slice_one(w, oy, ox):
        return lax.dynamic_slice(w, (oy + radius, ox + radius), (MB, MB))

    slice_all = jax.vmap(jax.vmap(slice_one))

    def sad_at(offy, offx):
        cand = slice_all(wins, offy, offx)        # (nby, nbx, MB, MB)
        return jnp.abs(curb - cand).sum(axis=(2, 3))

    zero = jnp.zeros((nby, nbx), jnp.int32)
    best_y, best_x = zero, zero
    best_sad = sad_at(zero, zero)
    # static unroll: len(steps) rounds of 9 probes, dy-major, first-wins
    for s in diamond_steps(radius):
        cy, cx = best_y, best_x
        for py in (-s, 0, s):
            for px in (-s, 0, s):
                oy = jnp.clip(cy + py, -radius, radius)
                ox = jnp.clip(cx + px, -radius, radius)
                sad = sad_at(oy, ox)
                better = sad < best_sad
                best_sad = jnp.where(better, sad, best_sad)
                best_y = jnp.where(better, oy, best_y)
                best_x = jnp.where(better, ox, best_x)
    return jnp.stack([best_y, best_x], axis=-1).astype(jnp.int32), best_sad


def block_sad(cur, ref, radius: int = 8, *, use_kernel: bool = False,
              dtype=None, search: str = "exhaustive"):
    """Returns (mv (nby, nbx, 2) int32, sad (nby, nbx) f32).

    cur/ref: (H, W) with H, W multiples of 16.  ``use_kernel`` routes
    through the Pallas kernels in ``repro.kernels.motion_sad`` (interpret
    mode on CPU).  The default path gathers one (MB+2R)² search window per
    macroblock and evaluates every candidate offset against those resident
    windows — the same per-block form as the kernel, so memory stays flat
    in the candidate count instead of materializing (2R+1)² whole-frame
    shifted copies like ``block_sad_scan``.  ``dtype`` (e.g. bf16) is the
    input storage dtype; SADs always accumulate in f32.  ``search``
    selects the exhaustive full search or the traced diamond search (see
    module docstring for the quality contract).
    """
    if search not in ("exhaustive", "diamond"):
        raise ValueError(f"unknown search strategy {search!r} "
                         "(expected 'exhaustive' or 'diamond')")
    if use_kernel and search == "diamond" \
            and not diamond_kernel_profitable(*cur.shape):
        # both variants share the probe schedule and SAD expression, so
        # results are identical either way — this is purely a perf route
        return block_sad_diamond(cur, ref, radius, dtype=dtype)
    if use_kernel:
        from repro.kernels.motion_sad.ops import motion_sad
        return motion_sad(cur, ref, radius=radius, dtype=dtype,
                          search=search)
    if search == "diamond":
        return block_sad_diamond(cur, ref, radius, dtype=dtype)
    curb, wins, nby, nbx = _search_prelude(cur, ref, radius, dtype)
    offs = _offsets(radius)

    def step(carry, off):
        best_sad, best_idx, idx = carry
        dy, dx = off[0] + radius, off[1] + radius
        cand = lax.dynamic_slice(wins, (0, 0, dy, dx), (nby, nbx, MB, MB))
        sad = jnp.abs(curb - cand).sum(axis=(2, 3))
        better = sad < best_sad
        best_sad = jnp.where(better, sad, best_sad)
        best_idx = jnp.where(better, idx, best_idx)
        return (best_sad, best_idx, idx + 1), None

    init = (jnp.full((nby, nbx), jnp.inf, f32),
            jnp.zeros((nby, nbx), jnp.int32), jnp.int32(0))
    (best_sad, best_idx, _), _ = lax.scan(step, init, offs)
    mv = offs[best_idx]  # (nby, nbx, 2)
    return mv.astype(jnp.int32), best_sad


def warp_blocks(ref, mv):
    """Motion compensation: gather 16×16 blocks of ``ref`` at MV offsets.

    ref: (H, W) with H, W multiples of 16; mv: (nby, nbx, 2) int32
    (dy, dx), any magnitude.  The anchor is edge-padded by 16 px and each
    output block copies the 16×16 block of the padded plane starting at
    ``s = clip(block*16 + 16 + d, 0, padded - 16)`` on each axis: a block
    moved past the padding reads the edge it moved past, never the
    opposite one.  Float32 out.

    No loop over blocks: the padded plane is laid out as rows of whole
    aligned 16×16 tiles (``(tiles, 256)``), ``s = 16*q + f`` picks the
    four tiles ``(q_y | q_y+1) × (q_x | q_x+1)`` the block straddles (one
    row gather each), and the sub-tile offset ``f`` cuts the block out of
    the square they make (``_sub_tile_shift``); every array stays
    lane-dense ``(blocks, 256)``.  The fused warp + residual kernel
    (``repro.kernels.qtransfer``) clips the motion to ±16 first, so it
    equals this function only where |mv| ≤ 16.
    """
    H, W = ref.shape
    nby, nbx = mv.shape[:2]
    pad = MB
    Hp, Wp = H + 2 * pad, W + 2 * pad
    ty, tx = Hp // MB, Wp // MB
    refp = jnp.pad(ref.astype(f32), pad, mode="edge")
    tiles = refp.reshape(ty, MB, tx, MB).transpose(0, 2, 1, 3) \
        .reshape(ty * tx, MB * MB)

    sy = jnp.clip(jnp.arange(nby)[:, None] * MB + pad + mv[..., 0],
                  0, Hp - MB).reshape(-1)
    sx = jnp.clip(jnp.arange(nbx)[None, :] * MB + pad + mv[..., 1],
                  0, Wp - MB).reshape(-1)
    qy, fy = sy // MB, sy % MB
    qx, fx = sx // MB, sx % MB
    # the second tile is never read where f == 0, so clamping it is safe
    qy1, qx1 = jnp.minimum(qy + 1, ty - 1), jnp.minimum(qx + 1, tx - 1)

    def tile(a, b):
        return jnp.take(tiles, a * tx + b, axis=0, mode="clip")

    blocks = _sub_tile_shift(tile(qy, qx), tile(qy, qx1), tile(qy1, qx),
                             tile(qy1, qx1), fy, fx)       # (blocks, 256)
    return blocks.reshape(nby, nbx, MB, MB).transpose(0, 2, 1, 3) \
        .reshape(H, W)


def _sub_tile_shift(tl, tr, bl, br, fy, fx):
    """The 16×16 block at (fy, fx) of each 32×32 square of four tiles.
    On a TPU the Pallas lane-gather kernel (``repro.kernels.tile_shift``);
    elsewhere the same selects in plain jnp.  Both move values unchanged,
    so they agree bit for bit."""
    from repro.kernels.motion_sad.ops import on_tpu
    if on_tpu():
        from repro.kernels.tile_shift import tile_shift
        return tile_shift(tl, tr, bl, br, fy, fx)
    return _shift_cols(_shift_rows(tl, bl, fy), _shift_rows(tr, br, fy), fx)


def _shift_rows(top, bottom, f):
    """Rows ``f .. f+15`` of each tile pair stacked top over bottom:
    (N, 256) row-major tiles, ``f`` (N,) in [0, 16)."""
    both = jnp.concatenate([top, bottom], axis=1)          # (N, 512)
    out = top
    for k in range(1, MB):
        out = jnp.where(f[:, None] == k, both[:, k * MB:k * MB + MB * MB],
                        out)
    return out


def _shift_cols(left, right, f):
    """Columns ``f .. f+15`` of each tile pair set left beside right:
    lane ``16r + c`` takes ``left[r, c+f]`` while ``c + f < 16``, else
    ``right[r, c+f-16]``."""
    n = left.shape[0]
    col = jnp.arange(MB * MB) % MB
    zero = jnp.zeros((n, MB), left.dtype)
    lpad = jnp.concatenate([left, zero], axis=1)           # (N, 272)
    rpad = jnp.concatenate([zero, right], axis=1)
    out = left
    for k in range(1, MB):
        shifted = jnp.where(col + k < MB, lpad[:, k:k + MB * MB],
                            rpad[:, k:k + MB * MB])
        out = jnp.where(f[:, None] == k, shifted, out)
    return out


def accumulate_mv(mvs):
    """Chain per-frame MVs into anchor-relative MVs (paper Fig. 7).

    mvs: (T, nby, nbx, 2) frame-to-previous-frame vectors.  Returns
    anchor-relative vectors by summation — the first-order approximation of
    following the codec reference index, adequate at small radii.
    """
    return jnp.cumsum(mvs, axis=0)

"""Encoder-path benchmark rows (``encoder_*`` in BENCH_pipeline.json).

Three motion-search implementations on the same P-frame (legacy
whole-frame scan vs the vmapped per-macroblock fallback vs the Pallas
kernel, f32 and bf16), plus the single-jit ``encode_chunk`` against
``encode_chunk_batched`` at 1..4 streams — the batched row's derived
field carries the measured speedup over encoding the same streams
sequentially.  Invoked from ``benchmarks.run``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def encoder_bench():
    # deferred: benchmarks.run imports this module inside main(), so a
    # module-level import back into run would create an import cycle
    from benchmarks.run import _kernel_tag, _timeit
    from repro.codec.motion import block_sad, block_sad_scan
    from repro.codec.video_codec import (VideoCodecConfig, encode_chunk,
                                         encode_chunk_batched)
    from repro.kernels.motion_sad.ops import motion_sad
    from repro.sim.video_source import StreamConfig, generate_chunk_batched

    rows = []
    H, W, T, radius = 64, 96, 4, 8
    cfgs = [StreamConfig(height=H, width=W, n_objects=3, seed=s)
            for s in range(4)]
    frames4 = generate_chunk_batched(cfgs, 0, T)[0]
    cur, ref = frames4[0, 1], frames4[0, 0]

    # ---- motion search: scan vs vmapped fallback vs kernel, f32 vs bf16.
    # Kernel rows carry BOTH oracle-relative ratios (two decimals — one
    # decimal rounded 0.95x up to "1.0x" and hid regressions): the
    # kernel-trajectory CI summary reads vs_scan/vs_fallback per PR.
    scan = jax.jit(lambda c, r: block_sad_scan(c, r, radius))
    us_scan = _timeit(lambda: scan(cur, ref), n=3)
    rows.append((f"encoder_block_sad_scan_{H}x{W}", us_scan,
                 f"r{radius}whole-frame"))
    vmapped = jax.jit(lambda c, r: block_sad(c, r, radius))
    us_v = _timeit(lambda: vmapped(cur, ref), n=3)
    rows.append((f"encoder_block_sad_vmapped_{H}x{W}", us_v,
                 f"vs_scan:{us_scan / max(us_v, 1e-9):.2f}x"))
    us_k = _timeit(lambda: motion_sad(cur, ref, radius=radius), n=3)
    rows.append((f"encoder_block_sad_kernel_{_kernel_tag()}_{H}x{W}", us_k,
                 f"vs_scan:{us_scan / max(us_k, 1e-9):.2f}x;"
                 f"vs_fallback:{us_v / max(us_k, 1e-9):.2f}x"))
    vm_bf = jax.jit(lambda c, r: block_sad(c, r, radius,
                                           dtype=jnp.bfloat16))
    us_vbf = _timeit(lambda: vm_bf(cur, ref), n=3)
    rows.append((f"encoder_block_sad_vmapped_bf16_{H}x{W}", us_vbf,
                 f"vs_f32:{us_v / max(us_vbf, 1e-9):.2f}x"))
    us_kbf = _timeit(lambda: motion_sad(cur, ref, radius=radius,
                                        dtype=jnp.bfloat16), n=3)
    rows.append((f"encoder_block_sad_kernel_bf16_{_kernel_tag()}_{H}x{W}",
                 us_kbf,
                 f"vs_f32:{us_k / max(us_kbf, 1e-9):.2f}x;"
                 f"vs_fallback:{us_vbf / max(us_kbf, 1e-9):.2f}x"))

    # ---- diamond search: traced coarse-to-fine, 37 of 289 candidates at
    # ±8 (quality contract in docs/fused_encoder.md, not bit-exactness)
    from repro.codec.motion import diamond_num_evals
    evals = f"evals:{diamond_num_evals(radius)}/{(2 * radius + 1) ** 2}"
    dia = jax.jit(lambda c, r: block_sad(c, r, radius, search="diamond"))
    us_d = _timeit(lambda: dia(cur, ref), n=3)
    rows.append((f"encoder_block_sad_diamond_{H}x{W}", us_d,
                 f"{evals};vs_exhaustive:{us_v / max(us_d, 1e-9):.2f}x"))
    us_dk = _timeit(lambda: motion_sad(cur, ref, radius=radius,
                                       search="diamond"),
                    n=3)
    rows.append((f"encoder_block_sad_kernel_diamond_{_kernel_tag()}_"
                 f"{H}x{W}", us_dk,
                 f"{evals};vs_scan:{us_scan / max(us_dk, 1e-9):.2f}x;"
                 f"vs_fallback:{us_d / max(us_dk, 1e-9):.2f}x"))

    # ---- diamond DISPATCH (block_sad with use_kernel=True): below
    # ~256 macroblocks the kernel trails the traced descent, so block_sad
    # statically routes small canvases to the fallback — this row must
    # track the fallback row above (vs_best ~1.0x), where the raw kernel
    # row trails it.  The 720p-shaped twin lives in
    # realistic_shape_bench (there the kernel side of the dispatch wins).
    disp = jax.jit(lambda c, r: block_sad(c, r, radius, use_kernel=True,
                                          search="diamond"))
    us_disp = _timeit(lambda: disp(cur, ref), n=3)
    rows.append((f"encoder_block_sad_diamond_dispatch_{H}x{W}", us_disp,
                 f"{evals};routed:fallback;"
                 f"vs_fallback:{us_d / max(us_disp, 1e-9):.2f}x;"
                 f"vs_kernel:{us_dk / max(us_disp, 1e-9):.2f}x"))

    # ---- chunk encode: single jit vs batched vmap over 1..4 streams
    cfg = VideoCodecConfig(quality=50.0, search_radius=radius)
    us_one = _timeit(lambda: encode_chunk(frames4[0], cfg), n=3)
    rows.append((f"encoder_chunk_single_{T}f_{H}x{W}", us_one, "one-jit"))
    cfg_bf = VideoCodecConfig(quality=50.0, search_radius=radius,
                              dtype="bfloat16")
    us_bf = _timeit(lambda: encode_chunk(frames4[0], cfg_bf), n=3)
    rows.append((f"encoder_chunk_single_bf16_{T}f_{H}x{W}", us_bf,
                 f"vs_f32:{us_one / max(us_bf, 1e-9):.2f}x"))
    for S in (1, 2, 4):
        batch = frames4[:S]
        us_b = _timeit(lambda: encode_chunk_batched(batch, cfg), n=3)
        seq = S * us_one
        fps = S * T / (us_b / 1e6)
        rows.append((f"encoder_chunk_batched_{S}stream", us_b,
                     f"fps:{fps:.0f};speedup_vs_sequential:"
                     f"{seq / max(us_b, 1e-9):.2f}x"))
    return rows

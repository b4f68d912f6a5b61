"""Shard the fused batched stream runtime over a device mesh.

``decode_execute_batched`` treats its leading axis as independent video
streams — there are no cross-stream collectives anywhere in the chunk
computation — so data-parallel placement over the mesh's "stream" axes is
exact: each device runs the same fused vmap over its local slice of
streams and the results concatenate back bit-for-bit.

``shard_streams(mesh, rules)`` returns a callable with the same signature
as ``decode_execute_batched``:

  * the stream axis is zero-padded up to a multiple of the mesh's stream
    extent (non-divisible stream counts — e.g. 3 streams on 4 devices —
    stay legal; padded lanes are computed and dropped),
  * stream-leading operands enter a ``shard_map`` region split over the
    rule table's "stream" axes; detector params are replicated,
  * outputs are unpadded back to the caller's stream count.

The single-device vmap stays the oracle: ``tests/test_stream_sharding.py``
forces a 4-device CPU platform in a subprocess and asserts bit-exact
parity for divisible and non-divisible stream counts.

``shard_encode(mesh, rules, cfg=...)`` is the encoder-side twin: it wraps
``encode_chunk_batched``'s body the same way (streams are just as
independent on the encode path), so camera-side chunk encoding scales over
the same "stream" mesh axes as edge-side execution
(``tests/test_fused_encoder.py`` holds its parity matrix).

``shard_roundtrip(mesh, rules, cfg=...)`` shards the WHOLE fused
encode->decode round trip (``repro.core.roundtrip``): each device runs
source-frames->HD-detections for its local slice of streams in one
program.  Mixed bitrate-ladder rungs are legal — the shard_map body is the
post-downscale heterogeneous form, so per-stream extents/QPs travel as
data while the shape-changing per-rung downscale stays outside the
region.  Padded stream lanes carry FULL-canvas extents (not zeros) so
their masked means never divide by zero.  ``RoundtripConfig.anchor_search``
rides through unchanged: the masked quality-ladder sweep is per-stream
data-parallel like everything else, and the ``anchor_q`` output follows the
same stream-leading out_specs as the rest of the result dict.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import AxisRules

f32 = jnp.float32


def stream_axis_names(mesh: Mesh, rules: AxisRules) -> tuple[str, ...]:
    """The rule table's "stream" axes that actually exist in ``mesh``."""
    return tuple(a for a in rules.mesh_axes("stream") if a in mesh.shape)


def stream_shard_count(mesh: Mesh, rules: AxisRules) -> int:
    """How many ways the stream axis splits on this mesh."""
    n = 1
    for a in stream_axis_names(mesh, rules):
        n *= mesh.shape[a]
    return n


def stream_partition_spec(mesh: Mesh, rules: AxisRules) -> P:
    axes = stream_axis_names(mesh, rules)
    if not axes:
        return P()
    return P(axes[0] if len(axes) == 1 else axes)


def stream_sharding(mesh: Mesh, rules: AxisRules) -> NamedSharding:
    return NamedSharding(mesh, stream_partition_spec(mesh, rules))


def pad_stream_axis(tree, n_shards: int):
    """Zero-pad every leaf's leading (stream) axis to a multiple of
    ``n_shards``.  Zero lanes are safe: each stream's chunk computation is
    independent and guarded against degenerate inputs (bw floors at 1e-6,
    F1 on empty boxes is finite), and the wrapper drops them on exit.
    Host (numpy) leaves are padded on the host, so a large input can go
    straight to its shards without first landing whole on one device."""
    def one(x):
        xp = np if isinstance(x, np.ndarray) else jnp
        x = xp.asarray(x)
        s = x.shape[0]
        s_pad = -(-s // n_shards) * n_shards
        if s_pad == s:
            return x
        pad = [(0, s_pad - s)] + [(0, 0)] * (x.ndim - 1)
        return xp.pad(x, pad)

    return jax.tree.map(one, tree)


def unpad_stream_axis(tree, s: int, mesh: Mesh):
    """Drop padded stream lanes from a sharded result.  A slice that cuts
    a sharded axis unevenly has no well-defined sharding (JAX refuses it
    under an explicit-axes mesh), so a padded leaf is replicated over
    ``mesh`` first; an unpadded leaf keeps its stream sharding."""
    replicated = NamedSharding(mesh, P())

    def one(x):
        if x.shape[0] == s:
            return x
        return jax.device_put(x, replicated)[:s]

    return jax.tree.map(one, tree)


def shard_encode(mesh: Mesh, rules: AxisRules, *, cfg):
    """Build the mesh-sharded twin of ``encode_chunk_batched``.

    Returns ``run(frames)`` where frames is (S, T, H, W): the stream axis
    is zero-padded up to the mesh's stream extent, each device encodes its
    local slice of streams through the single-jit codec body, and outputs
    unpad back to S.  Zero-frame lanes are safe — the codec is total on
    constant frames (the all-ties motion search resolves first-wins) — and
    they are dropped on exit.  ``cfg`` (``VideoCodecConfig``) is bound at
    build time: it is a static jit argument."""
    from repro.codec.video_codec import _encode_batch

    spec = stream_partition_spec(mesh, rules)
    n_shards = stream_shard_count(mesh, rules)

    sharded = jax.jit(jax.shard_map(
        lambda f: _encode_batch(f, cfg), mesh=mesh,
        in_specs=(spec,), out_specs=spec, check_vma=False,
    ))

    def run(frames):
        frames = jnp.asarray(frames)
        s = frames.shape[0]
        (padded,) = pad_stream_axis((frames,), n_shards)
        out = sharded(padded)
        return unpad_stream_axis(out, s, mesh)

    return run


def shard_roundtrip(mesh: Mesh, rules: AxisRules, *, cfg):
    """Build the mesh-sharded twin of ``roundtrip_batched`` /
    ``roundtrip_ladder_batched``.

    Returns ``run(raw, gt_boxes, gt_valid, detector_params, *, tr1, tr2,
    bw_kbps, queue_delay, levels=None)`` where raw is (S, T, H, W) source
    frames and the keyword scalars broadcast to (S,).  ``levels`` (host
    tuple, one ladder rung per stream) defaults to ``cfg.level`` for all
    streams; mixed rungs run through the padded heterogeneous encode, so
    one shard_map region serves the whole mixed-ladder stream set.  The
    stream axis is zero-padded to the mesh's stream extent; padded lanes
    get full-canvas extents (a zero extent would poison the masked means
    with 0/0) and are dropped on exit.  ``cfg`` (``RoundtripConfig``) is
    bound at build time — it is a static jit argument."""
    from repro.core.roundtrip import (_downscale_pad, _roundtrip_ladder_body,
                                      ladder_batch_arrays)

    spec = stream_partition_spec(mesh, rules)
    n_shards = stream_shard_count(mesh, rules)

    def body(raw, lr_pad, extents, qualities, gb, gv, params, t1, t2,
             bw, qd):
        return _roundtrip_ladder_body(raw, lr_pad, extents, qualities, gb,
                                      gv, params, t1, t2, bw, qd, cfg)

    sharded = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, spec, P(), spec, spec,
                  spec, spec),
        out_specs=spec, check_vma=False,
    ))

    def run(raw, gt_boxes, gt_valid, detector_params, *, tr1, tr2, bw_kbps,
            queue_delay=0.0, levels=None):
        # host frames stay on the host until they go to their shards: a
        # 9-stream 1080p chunk is 2.2 GB, too much to stage on one device
        # next to that device's own share of the program
        raw = np.asarray(raw, np.float32) if isinstance(raw, np.ndarray) \
            else jnp.asarray(raw, f32)
        s = raw.shape[0]
        levels = tuple(levels) if levels is not None else (cfg.level,) * s
        lr_pad = _downscale_pad(raw, levels)
        extents, qualities = ladder_batch_arrays(levels, *raw.shape[2:])
        streamed = (raw, lr_pad, gt_boxes, gt_valid,
                    jnp.broadcast_to(jnp.asarray(tr1, f32), (s,)),
                    jnp.broadcast_to(jnp.asarray(tr2, f32), (s,)),
                    jnp.broadcast_to(jnp.asarray(bw_kbps, f32), (s,)),
                    jnp.broadcast_to(jnp.asarray(queue_delay, f32), (s,)))
        r, lp, gb, gv, t1, t2, bw, qd = pad_stream_axis(streamed, n_shards)
        r = jax.device_put(r, NamedSharding(mesh, spec))
        pad = r.shape[0] - s
        if pad:
            # padded lanes: full canvas extent, nominal quality
            extents = jnp.concatenate(
                [extents, jnp.tile(jnp.asarray(lp.shape[2:], jnp.int32),
                                   (pad, 1))])
            qualities = jnp.concatenate([qualities, jnp.full((pad,), 50.0,
                                                             f32)])
        out = sharded(r, lp, extents, qualities, gb, gv, detector_params,
                      t1, t2, bw, qd)
        return unpad_stream_axis(out, s, mesh)

    return run


def shard_streams(mesh: Mesh, rules: AxisRules, *, det_cfg,
                  costs=None):
    """Build the mesh-sharded twin of ``decode_execute_batched``.

    Returns ``run(enc, types, anchor_hd, gt_boxes, gt_valid,
    detector_params, *, bw_kbps, queue_delay, total_bits)`` where every
    positional operand and the three keyword scalars carry a leading
    stream axis of identical extent S.  S need not divide the mesh's
    stream extent.  ``det_cfg``/``costs`` are bound at build time (they
    are static jit arguments)."""
    from repro.core.hybrid_decoder import PipelineCosts, _execute_batch

    costs = costs or PipelineCosts()
    spec = stream_partition_spec(mesh, rules)
    n_shards = stream_shard_count(mesh, rules)

    def body(e, ty, ah, gb, gv, params, bw, qd, tb):
        return _execute_batch(e, ty, ah, gb, gv, params, det_cfg,
                              bw, qd, tb, costs)

    sharded = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, P(), spec, spec, spec),
        out_specs=spec, check_vma=False,
    ))

    def run(enc, types, anchor_hd, gt_boxes, gt_valid, detector_params, *,
            bw_kbps, queue_delay, total_bits):
        types = jnp.asarray(types)
        s = types.shape[0]
        streamed = (enc, types, anchor_hd, gt_boxes, gt_valid,
                    jnp.broadcast_to(jnp.asarray(bw_kbps, f32), (s,)),
                    jnp.broadcast_to(jnp.asarray(queue_delay, f32), (s,)),
                    jnp.broadcast_to(jnp.asarray(total_bits, f32), (s,)))
        e, ty, ah, gb, gv, bw, qd, tb = pad_stream_axis(streamed, n_shards)
        out = sharded(e, ty, ah, gb, gv, detector_params, bw, qd, tb)
        return unpad_stream_axis(out, s, mesh)

    return run

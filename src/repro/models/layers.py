"""Shared neural-net layers for the backbone zoo (pure JAX, pjit-friendly).

Conventions
-----------
* activations: (batch, seq, d) or NHWC for vision.
* attention tensors: q (B, Sq, H, D); k/v (B, Sk, Hk, D) with GQA groups
  G = H // Hk.
* all matmuls accumulate in fp32 (``preferred_element_type``), softmax in
  fp32; outputs cast back to the activation dtype.
* attention is *chunked* (online softmax over KV blocks) so no S×S tensor is
  ever materialized — this is the XLA path; the Pallas flash kernel in
  ``repro.kernels.flash_attention`` is the TPU-optimized path.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

f32 = jnp.float32
NEG_INF = -1e30

# Dry-run cost-analysis mode: XLA's HLO cost analysis counts a while-loop
# body ONCE regardless of trip count, so the roofline dry-run fully unrolls
# every scan (layers, attention KV chunks, SWA q-blocks) to obtain exact
# FLOP/byte/collective counts.  Normal execution keeps rolled scans.
_DRYRUN_UNROLL = False


def set_dryrun_unroll(v: bool) -> None:
    global _DRYRUN_UNROLL
    _DRYRUN_UNROLL = v


def scan_unroll(length: int) -> int:
    return length if _DRYRUN_UNROLL else 1


def constrain(x, *logical_axes):
    """Activation sharding constraint from the ambient ShardCtx.

    No-op outside a ctx (CPU smoke tests) and inside shard_map bodies.
    Non-divisible dims demote to replicated automatically.
    """
    from repro.distributed.context import current_ctx
    from repro.distributed.sharding import named_sharding

    ctx = current_ctx()
    if ctx is None:
        return x
    sh = named_sharding(ctx.mesh, logical_axes, ctx.rules, x.shape)
    return jax.lax.with_sharding_constraint(x, sh)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------
def rms_norm(x, weight, eps: float = 1e-6):
    xf = x.astype(f32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * weight.astype(f32)).astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-6):
    xf = x.astype(f32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * weight.astype(f32) + bias.astype(f32)).astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary embeddings
# --------------------------------------------------------------------------
def rope_freqs(head_dim: int, fraction: float, theta: float) -> jnp.ndarray:
    """Inverse frequencies for the rotated sub-dimension."""
    rot = int(head_dim * fraction)
    rot -= rot % 2
    return 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=f32) / rot))


def apply_rope(x, positions, *, fraction: float = 1.0, theta: float = 10000.0):
    """x: (B, S, H, D); positions: (S,) or (B, S)."""
    D = x.shape[-1]
    rot = int(D * fraction)
    rot -= rot % 2
    inv = rope_freqs(D, fraction, theta)  # (rot/2,)
    pos = positions.astype(f32)
    if pos.ndim == 1:
        pos = pos[None, :]  # (1, S)
    ang = pos[..., None] * inv[None, None, :]           # (B?, S, rot/2)
    sin = jnp.sin(ang)[:, :, None, :]                   # (B?, S, 1, rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., ::2], xr[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([yr.astype(x.dtype), xp], axis=-1)


# --------------------------------------------------------------------------
# Attention — chunked online-softmax (full/causal) and SWA q-block paths
# --------------------------------------------------------------------------
def _repeat_kv(k, n_heads: int):
    """(B, S, Hk, D) -> (B, S, H, D) by repeating each kv head G times.

    Keeps the einsums flat over H so tensor-parallel head sharding works for
    any (Hk, TP) combination; per-device the repeat holds only the local
    slice, and the Pallas flash kernel avoids materializing it entirely.
    """
    B, S, Hk, D = k.shape
    G = n_heads // Hk
    if G == 1:
        return k
    return jnp.repeat(k, G, axis=2)


def chunked_attention(q, k, v, *, causal: bool, q_offset=0,
                      kv_positions=None, chunk: int = 1024):
    """Online-softmax attention scanning over KV chunks.

    q: (B, Sq, H, D); k, v: (B, Sk, Hk, D).  ``q_offset`` is the absolute
    position of q[0] (for causal masking during chunked prefill / decode).
    ``kv_positions``: (Sk,) absolute positions of cache slots (ring caches);
    defaults to arange.  Slots with position < 0 are masked out.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    q = constrain(q.astype(jnp.bfloat16), "batch", None, "tensor", None)
    k = constrain(_repeat_kv(k, H).astype(jnp.bfloat16),
                  "batch", None, "tensor", None)
    v = constrain(_repeat_kv(v, H).astype(jnp.bfloat16),
                  "batch", None, "tensor", None)
    if kv_positions is None:
        kv_positions = jnp.arange(Sk, dtype=jnp.int32)
    q_pos = q_offset + jnp.arange(Sq, dtype=jnp.int32)

    chunk = min(chunk, Sk)
    if Sk % chunk:
        chunk = Sk  # fallback: single chunk
    n_chunks = Sk // chunk
    kc = k.reshape(B, n_chunks, chunk, H, D)
    vc = v.reshape(B, n_chunks, chunk, H, D)
    pc = kv_positions.reshape(n_chunks, chunk)
    scale = D ** -0.5

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, pb = inp  # (B, C, H, D), (C,)
        s = jnp.einsum("bqhd,bchd->bhqc", q, kb,
                       preferred_element_type=f32) * scale
        mask = pb[None, :] >= 0
        if causal:
            mask = mask & (pb[None, :] <= q_pos[:, None])
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqc,bchd->bhqd", p.astype(jnp.bfloat16), vb,
                        preferred_element_type=f32)
        acc_new = acc * corr[..., None] + pv
        m_new = constrain(m_new, "batch", "tensor", None)
        l_new = constrain(l_new, "batch", "tensor", None)
        acc_new = constrain(acc_new, "batch", "tensor", None, None)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), NEG_INF, f32)
    l0 = jnp.zeros((B, H, Sq), f32)
    a0 = jnp.zeros((B, H, Sq, D), f32)
    (m, l, acc), _ = lax.scan(
        step, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), pc),
        unroll=scan_unroll(n_chunks))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = jnp.moveaxis(out, 1, 2)                   # (B,Sq,H,D)
    return constrain(out.astype(jnp.bfloat16), "batch", None, "tensor", None)


def swa_attention(q, k, v, *, window: int, q_offset=0, q_block: int = 1024):
    """Sliding-window causal attention via q-block scan + KV dynamic slice.

    FLOPs scale as Sq×(window+q_block) instead of Sq×Sk — this is the
    sub-quadratic path used by mixtral configs.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    q = constrain(q.astype(jnp.bfloat16), "batch", None, "tensor", None)
    k = constrain(_repeat_kv(k, H).astype(jnp.bfloat16),
                  "batch", None, "tensor", None)
    v = constrain(_repeat_kv(v, H).astype(jnp.bfloat16),
                  "batch", None, "tensor", None)
    qb = min(q_block, Sq)
    if Sq % qb:
        qb = Sq
    nq = Sq // qb
    span = min(window + qb, Sk)
    scale = D ** -0.5
    qs = q.reshape(B, nq, qb, H, D)

    def step(i):
        qi = qs[:, i]                                          # (B,qb,H,D)
        q_pos = q_offset + i * qb + jnp.arange(qb, dtype=jnp.int32)
        ks_raw = q_offset + i * qb + qb - span                 # window start
        ks = jnp.clip(ks_raw, 0, Sk - span)
        kb = lax.dynamic_slice_in_dim(k, ks, span, axis=1)
        vb = lax.dynamic_slice_in_dim(v, ks, span, axis=1)
        k_pos = ks + jnp.arange(span, dtype=jnp.int32)
        s = jnp.einsum("bqhd,bchd->bhqc", qi, kb,
                       preferred_element_type=f32) * scale
        mask = (k_pos[None, :] <= q_pos[:, None]) & (
            q_pos[:, None] - k_pos[None, :] < window)
        s = jnp.where(mask[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqc,bchd->bqhd", p.astype(jnp.bfloat16), vb,
                       preferred_element_type=f32)
        return constrain(o.astype(jnp.bfloat16),
                         "batch", None, "tensor", None)

    _, out = lax.scan(lambda c, i: (c, step(i)), None,
                      jnp.arange(nq), unroll=scan_unroll(nq))  # (nq,B,qb,H,D)
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, D)
    return constrain(out, "batch", None, "tensor", None)


def decode_attention(q, k_cache, v_cache, *, cache_positions, pos,
                     window: int | None = None):
    """Single-token decode attention over a (possibly ring) KV cache.

    q: (B, 1, H, D); caches: (B, S, Hk, D); cache_positions: (S,) int32 with
    -1 for unwritten slots; pos: scalar current position.
    """
    B, _, H, D = q.shape
    Hk = k_cache.shape[2]
    qg = q.reshape(B, 1, Hk, H // Hk, D).astype(jnp.bfloat16)
    qg = constrain(qg, "batch", None, None, None, None)
    s = jnp.einsum("bqhgd,bshd->bhgqs", qg, k_cache.astype(jnp.bfloat16),
                   preferred_element_type=f32) * D ** -0.5
    s = constrain(s, "batch", None, None, None, "seq_kv")
    valid = (cache_positions >= 0) & (cache_positions <= pos)
    if window is not None:
        valid = valid & (pos - cache_positions < window)
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqs,bshd->bhgqd", p.astype(jnp.bfloat16),
                   v_cache.astype(jnp.bfloat16), preferred_element_type=f32)
    o = jnp.moveaxis(o, 3, 1).reshape(B, 1, H, D)
    return constrain(o.astype(q.dtype), "batch", None, None, None)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------
def swiglu(x, w1, w3, w2):
    h = jnp.einsum("...d,df->...f", x, w1, preferred_element_type=f32)
    g = jnp.einsum("...d,df->...f", x, w3, preferred_element_type=f32)
    h = (jax.nn.silu(h) * g).astype(x.dtype)
    # bf16 output: the ff dim is tensor-sharded, so this matmul's partial
    # sums are all-reduced -- keep the wire payload in bf16.
    return jnp.einsum("...f,fd->...d", h, w2)


def gelu_mlp(x, w1, b1, w2, b2):
    h = jnp.einsum("...d,df->...f", x, w1, preferred_element_type=f32)
    h = jax.nn.gelu(h + b1.astype(f32)).astype(x.dtype)
    y = jnp.einsum("...f,fd->...d", h, w2)   # bf16 wire (see swiglu)
    return (y.astype(f32) + b2.astype(f32)).astype(x.dtype)


# --------------------------------------------------------------------------
# Mixture of Experts
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    norm_topk: bool = True          # qwen renormalizes top-k probs


def router_topk(x, w_router, moe: MoEConfig):
    """Returns (expert_idx (T,k), weights (T,k), aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x.astype(f32), w_router.astype(f32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, moe.top_k)
    if moe.norm_topk:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch-style)
    me = probs.mean(0)
    ce = jnp.zeros(moe.n_experts).at[idx.reshape(-1)].add(
        1.0 / idx.size)
    aux = moe.n_experts * jnp.sum(me * ce)
    return idx, w.astype(x.dtype), aux


def moe_sorted_dispatch(x, w_router, w1, w3, w2, moe: MoEConfig):
    """Dropping MoE via sort-based dispatch into (E, C, d) capacity buffers.

    x: (T, d) tokens local to this shard.  Expert weights: w1/w3 (E, d, f),
    w2 (E, f, d).  FLOPs-honest: the only matmuls are the E×C×d×f expert
    GEMMs; dispatch/combine are gathers + scatters.
    """
    T, d = x.shape
    E, k = moe.n_experts, moe.top_k
    C = max(k, int(T * k * moe.capacity_factor / E + 0.999))
    C = min(C, T)
    idx, w, aux = router_topk(x, w_router, moe)
    eflat = idx.reshape(-1)                       # (T*k,)
    order = jnp.argsort(eflat, stable=True)
    sorted_e = eflat[order]
    counts = jnp.bincount(eflat, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e]
    tok = order // k
    buf = jnp.zeros((E, C, d), x.dtype)
    buf = buf.at[sorted_e, pos].set(x[tok], mode="drop")
    h = jnp.einsum("ecd,edf->ecf", buf, w1, preferred_element_type=f32)
    g = jnp.einsum("ecd,edf->ecf", buf, w3, preferred_element_type=f32)
    h = (jax.nn.silu(h) * g).astype(x.dtype)
    y = jnp.einsum("ecf,efd->ecd", h, w2,
                   preferred_element_type=f32).astype(x.dtype)
    contrib = y.at[sorted_e, pos].get(mode="fill", fill_value=0.0)
    contrib = contrib * w.reshape(-1)[order][:, None]
    out = jnp.zeros((T, d), x.dtype).at[tok].add(contrib)
    return out, aux


def moe_gathered_experts(x, w_router, w1, w3, w2, moe: MoEConfig):
    """Decode-shape MoE: per-token gather of its k experts' weights.

    FLOPs-honest (2·T·k·d·f per matmul); weight bytes are duplicated when
    T·k > E (noted in the roofline analysis).  Used when T is tiny.
    """
    T, d = x.shape
    idx, w, aux = router_topk(x, w_router, moe)   # (T,k)
    w1g = w1[idx]                                 # (T,k,d,f)
    w3g = w3[idx]
    w2g = w2[idx]                                 # (T,k,f,d)
    h = jnp.einsum("td,tkdf->tkf", x, w1g, preferred_element_type=f32)
    g = jnp.einsum("td,tkdf->tkf", x, w3g, preferred_element_type=f32)
    h = (jax.nn.silu(h) * g).astype(x.dtype)
    y = jnp.einsum("tkf,tkfd->tkd", h, w2g, preferred_element_type=f32)
    out = jnp.einsum("tkd,tk->td", y.astype(f32), w.astype(f32))
    return out.astype(x.dtype), aux


def _moe_local(xf, w_router, w1, w3, w2, moe: MoEConfig):
    """Dispatch-path choice for a *local* (unsharded) token block.

    sorted dispatch reads each expert's weights exactly once -> wins whenever
    T·k >= E; the gathered path reads only the k selected experts -> wins for
    tiny token counts (B=1 decode).
    """
    if xf.shape[0] * moe.top_k >= moe.n_experts:
        return moe_sorted_dispatch(xf, w_router, w1, w3, w2, moe)
    return moe_gathered_experts(xf, w_router, w1, w3, w2, moe)


def moe_block(x, w_router, w1, w3, w2, moe: MoEConfig):
    """x: (B, S, d) -> (B, S, d).

    With an ambient ShardCtx and a shardable batch, the dispatch runs inside
    an explicit ``shard_map`` over the batch axes so the argsort/scatter are
    *local* to each shard (a global argsort would all-gather every token).
    Expert weights enter the region all-gathered over fsdp but still sharded
    over the tensor axis (ff dim); the second GEMM's partial sums are
    reduced with one psum over the tensor axis after token combine.
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed.context import current_ctx

    B, S, d = x.shape
    ctx = current_ctx()
    use_sm = (
        ctx is not None
        and len(ctx.batch_axes) > 0
        and B % ctx.axis_size(ctx.batch_axes) == 0
        and ctx.axis_size(ctx.tensor_axes) > 1
        and w1.shape[-1] % ctx.axis_size(ctx.tensor_axes) == 0
        # shard_map's in_specs force an all-gather of the FSDP-sharded
        # expert weights (~all params!) — only worth it when the token
        # batch is large enough that a global argsort would cost more.
        # Decode-sized batches stay on auto-SPMD, which keeps weights
        # sharded and psums the (tiny) activation partials instead.
        and B * S >= 4096
    )
    if not use_sm:
        out, aux = _moe_local(x.reshape(B * S, d), w_router, w1, w3, w2, moe)
        return out.reshape(B, S, d), aux

    batch = ctx.batch_axes if len(ctx.batch_axes) > 1 else ctx.batch_axes[0]
    tensor = ctx.tensor_axes[0]

    def body(xb, wr, a1, a3, a2):
        Bl = xb.shape[0]
        xf = xb.reshape(Bl * S, d)
        idx, w, aux = router_topk(xf, wr, moe)
        T, E, k = xf.shape[0], moe.n_experts, moe.top_k
        if T * k >= E:
            C = min(max(k, int(T * k * moe.capacity_factor / E + 0.999)), T)
            eflat = idx.reshape(-1)
            order = jnp.argsort(eflat, stable=True)
            sorted_e = eflat[order]
            counts = jnp.bincount(eflat, length=E)
            starts = jnp.cumsum(counts) - counts
            pos = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e]
            tok = order // k
            buf = jnp.zeros((E, C, d), xf.dtype)
            buf = buf.at[sorted_e, pos].set(xf[tok], mode="drop")
            h = jnp.einsum("ecd,edf->ecf", buf, a1, preferred_element_type=f32)
            g = jnp.einsum("ecd,edf->ecf", buf, a3, preferred_element_type=f32)
            h = (jax.nn.silu(h) * g).astype(xf.dtype)
            y = jnp.einsum("ecf,efd->ecd", h, a2,
                           preferred_element_type=f32).astype(xf.dtype)
            contrib = y.at[sorted_e, pos].get(mode="fill", fill_value=0.0)
            contrib = contrib * w.reshape(-1)[order][:, None]
            out = jnp.zeros((T, d), xf.dtype).at[tok].add(contrib)
        else:
            h = jnp.einsum("td,tkdf->tkf", xf, a1[idx], preferred_element_type=f32)
            g = jnp.einsum("td,tkdf->tkf", xf, a3[idx], preferred_element_type=f32)
            h = (jax.nn.silu(h) * g).astype(xf.dtype)
            y = jnp.einsum("tkf,tkfd->tkd", h, a2[idx], preferred_element_type=f32)
            out = jnp.einsum("tkd,tk->td", y, w.astype(f32)).astype(xf.dtype)
        out = lax.psum(out, tensor)           # partial over ff shards
        aux = lax.pmean(aux, batch)
        return out.reshape(Bl, S, d), aux

    out, aux = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(batch), P(), P(None, None, tensor), P(None, None, tensor),
                  P(None, tensor, None)),
        out_specs=(P(batch), P()), check_vma=False,
    )(x, w_router, w1, w3, w2)
    return out, aux

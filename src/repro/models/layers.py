"""Core neural layers: norms, RoPE/M-RoPE, chunked attention, MLP, MoE.

Design rules (see DESIGN.md §3/§4):
- pure functions over param dicts (pytrees); no module framework.
- attention is computed flash-style (online softmax over KV chunks inside a
  ``lax.scan``) so 32k-token prefill never materialises an S×S score matrix.
- MoE uses sort-based capacity dispatch into an (E, C, d) buffer — the
  TPU-native formulation (batched expert einsum on the MXU), with a
  sharding constraint placing experts on the ``model`` mesh axis.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Initialisers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else fan_in ** -0.5
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (w * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dt)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE + sectioned M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies for the rotary halves (head_dim//2,)."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Standard RoPE. x: (..., S, H, D); positions: (..., S) int32."""
    if theta <= 0:
        return x
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)  # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def apply_mrope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    theta: float,
    sections: Tuple[int, ...],
) -> jnp.ndarray:
    """Qwen2-VL sectioned M-RoPE.

    x: (B, S, H, D). positions: (B, 3, S) — temporal/height/width streams.
    ``sections`` partitions the rotary half-dim; section i rotates with
    position stream i. sum(sections) == D // 2.
    """
    if theta <= 0:
        return x
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(x.shape[-1], theta)  # (half,)
    # angles per stream: (B, 3, S, half)
    angles_all = positions[..., None].astype(jnp.float32) * freqs
    # select stream per frequency-section: section_ids[h] in {0,1,2}
    section_ids = jnp.repeat(
        jnp.arange(len(sections)), jnp.array(sections), total_repeat_length=half
    )  # (half,) static
    sel = jax.nn.one_hot(section_ids, len(sections), dtype=jnp.float32)  # (half, 3)
    angles = jnp.einsum("bksh,hk->bsh", angles_all, sel)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention
# ---------------------------------------------------------------------------


NEG_INF = -1e30


def _attn_chunk_mask(
    q_pos: jnp.ndarray, k_pos: jnp.ndarray, causal: bool, window: int
) -> jnp.ndarray:
    """(Qc, Kc) boolean mask: True = attend."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def chunked_attention(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Sk, KV, D)
    v: jnp.ndarray,  # (B, Sk, KV, D)
    *,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 1024,
    k_chunk: int = 1024,
    q_offset: int = 0,
    block_skip: bool = True,
    differentiable: bool = True,
    max_unroll: int = 8,
    unroll_kv: bool = False,
) -> jnp.ndarray:
    """Memory-efficient attention with online softmax (flash-style).

    Never materialises more than (B, KV, G, Qc, Kc) scores. GQA is handled
    by grouping query heads over KV heads. Causal block skip — not
    computing fully-masked KV blocks, which halves causal FLOPs vs a
    masked-full implementation — comes in two flavours:

    - **unrolled** (differentiable, used in training): python-unrolled
      query blocks, each scanning only its static KV prefix. HLO grows
      ~n_q-fold, so only used when n_q <= max_unroll.
    - **dynamic** (non-differentiable, used in prefill): scanned query
      blocks with a bounded ``fori_loop`` over KV blocks — compact HLO at
      any sequence length, but reverse-mode AD rejects the dynamic trip
      count.

    Otherwise falls back to the masked full scan (always differentiable).
    """
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    # pad to multiples
    n_q = -(-Sq // q_chunk)
    n_k = -(-Sk // k_chunk)
    pad_q = n_q * q_chunk - Sq
    pad_k = n_k * k_chunk - Sk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v

    scale = D ** -0.5
    # (n_q, B, Qc, KV, G, D)
    qs = qp.reshape(B, n_q, q_chunk, KV, G, D).transpose(1, 0, 2, 3, 4, 5)
    ks = kp.reshape(B, n_k, k_chunk, KV, D).transpose(1, 0, 2, 3, 4)
    vs = vp.reshape(B, n_k, k_chunk, KV, D).transpose(1, 0, 2, 3, 4)

    q_pos_base = q_offset + jnp.arange(n_q * q_chunk).reshape(n_q, q_chunk)
    k_pos_base = jnp.arange(n_k * k_chunk).reshape(n_k, k_chunk)

    def kv_step_fn(q_blk, q_pos):
        def kv_step(acc, ki_inputs):
            k_blk, v_blk, k_pos = ki_inputs
            m_prev, l_prev, o_prev = acc
            # scores: (B, KV, G, Qc, Kc). Operands stay in their native
            # dtype (bf16 on TPU) with f32 MXU accumulation — explicit f32
            # casts here would double the HBM traffic of the QK^T and PV
            # matmuls (measured in EXPERIMENTS.md §Perf).
            s = jnp.einsum("bqkgd,bckd->bkgqc", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            mask = _attn_chunk_mask(q_pos, k_pos, causal, window)
            mask &= (k_pos < Sk)[None, :]  # key padding
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqc,bckd->bkgqd", p.astype(v_blk.dtype), v_blk,
                            preferred_element_type=jnp.float32)
            o_new = o_prev * corr[..., None] + pv
            return (m_new, l_new, o_new), None

        return kv_step

    def init_acc():
        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        o0 = jnp.zeros((B, KV, G, q_chunk, D), jnp.float32)
        return m0, l0, o0

    def finish(m, l, o):
        return (o / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)

    skippable = causal and block_skip and q_offset == 0 and Sq == Sk \
        and window == 0

    if skippable and (unroll_kv or (differentiable and n_q <= max_unroll)):
        # --- unrolled structural skip (differentiable)
        outs = []
        for qi in range(n_q):
            step = kv_step_fn(qs[qi], q_pos_base[qi])
            if unroll_kv:  # full unroll: true HLO cost visible to XLA
                acc = init_acc()
                for kj in range(qi + 1):
                    acc, _ = step(acc, (ks[kj], vs[kj], k_pos_base[kj]))
                m, l, o = acc
            else:
                (m, l, o), _ = jax.lax.scan(
                    step, init_acc(),
                    (ks[: qi + 1], vs[: qi + 1], k_pos_base[: qi + 1]))
            outs.append(finish(m, l, o))
        outs = jnp.stack(outs)  # (n_q, B, KV, G, Qc, D)
    elif skippable and not differentiable:
        # --- dynamic structural skip (prefill; no reverse-mode AD)
        def q_block(carry, qi_inputs):
            qi, q_blk, q_pos = qi_inputs
            step = kv_step_fn(q_blk, q_pos)

            def body(kj, acc):
                inp = (
                    jax.lax.dynamic_index_in_dim(ks, kj, 0, keepdims=False),
                    jax.lax.dynamic_index_in_dim(vs, kj, 0, keepdims=False),
                    jax.lax.dynamic_index_in_dim(k_pos_base, kj, 0,
                                                 keepdims=False),
                )
                acc2, _ = step(acc, inp)
                return acc2

            m, l, o = jax.lax.fori_loop(0, qi + 1, body, init_acc())
            return carry, finish(m, l, o)

        _, outs = jax.lax.scan(
            q_block, None, (jnp.arange(n_q), qs, q_pos_base))
    elif unroll_kv:
        # --- fully unrolled masked attention (cost calibration)
        outs_l = []
        for qi in range(n_q):
            step = kv_step_fn(qs[qi], q_pos_base[qi])
            acc = init_acc()
            for kj in range(n_k):
                acc, _ = step(acc, (ks[kj], vs[kj], k_pos_base[kj]))
            outs_l.append(finish(*acc))
        outs = jnp.stack(outs_l)
    else:
        # --- masked full scan (fallback; differentiable)
        def q_block(carry, qi_inputs):
            q_blk, q_pos = qi_inputs
            step = kv_step_fn(q_blk, q_pos)
            (m, l, o), _ = jax.lax.scan(step, init_acc(),
                                        (ks, vs, k_pos_base))
            return carry, finish(m, l, o)

        _, outs = jax.lax.scan(q_block, None, (qs, q_pos_base))

    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, n_q * q_chunk, H, D)
    return out[:, :Sq]


def decode_attention(
    q: jnp.ndarray,  # (B, 1, H, D)
    k_cache: jnp.ndarray,  # (B, W, KV, D)
    v_cache: jnp.ndarray,  # (B, W, KV, D)
    cache_pos: jnp.ndarray,  # (B, W) int32, -1 = empty
    pos: jnp.ndarray,  # (B,) current absolute position
    *,
    window: int = 0,
) -> jnp.ndarray:
    """Single-token attention against a (possibly ring-buffer) KV cache."""
    B, W, KV, D = k_cache.shape
    H = q.shape[2]
    G = H // KV
    scale = D ** -0.5
    qh = q.reshape(B, KV, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bwkd->bkgw", qh, k_cache.astype(jnp.float32)) * scale
    valid = (cache_pos >= 0) & (cache_pos <= pos[:, None])
    if window > 0:
        valid &= pos[:, None] - cache_pos < window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgw,bwkd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + norm variants)
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ArchConfig, dtype) -> Params:
    d, H, KV = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    Dh = cfg.resolved_head_dim()
    ks = jax.random.split(key, 5)
    p: Params = {
        "wq": dense_init(ks[0], (d, H * Dh), dtype),
        "wk": dense_init(ks[1], (d, KV * Dh), dtype),
        "wv": dense_init(ks[2], (d, KV * Dh), dtype),
        "wo": dense_init(ks[3], (H * Dh, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * Dh,), dtype)
        p["bk"] = jnp.zeros((KV * Dh,), dtype)
        p["bv"] = jnp.zeros((KV * Dh,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((Dh,), dtype)
        p["k_norm"] = jnp.ones((Dh,), dtype)
    return p


def _project_qkv(p: Params, x, cfg: ArchConfig):
    B, S, d = x.shape
    H, KV = cfg.n_heads, cfg.n_kv_heads
    Dh = cfg.resolved_head_dim()
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, KV, Dh)
    v = v.reshape(B, S, KV, Dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def attention_block(
    p: Params,
    x: jnp.ndarray,  # (B, S, d)
    cfg: ArchConfig,
    positions: jnp.ndarray,  # (B, S) or (B, 3, S) for mrope
    *,
    causal: bool = True,
    window: int = 0,
    differentiable: bool = True,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Full-sequence attention. Returns (out, (k, v)) for cache priming."""
    q, k, v = _project_qkv(p, x, cfg)
    if cfg.mrope:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.use_flash_kernel and causal and window == 0 and differentiable is False:
        # Pallas flash kernel (forward-only paths: prefill/serving — the
        # kernel has no custom VJP; training keeps the jnp chunked path)
        from repro.kernels.ops import flash_mha

        out = flash_mha(q, k, v, causal=True)
    else:
        out = chunked_attention(q, k, v, causal=causal, window=window,
                                differentiable=differentiable,
                                q_chunk=cfg.attn_chunk, k_chunk=cfg.attn_chunk,
                                unroll_kv=cfg.unroll_attn)
    B, S, _, _ = q.shape
    out = out.reshape(B, S, -1) @ p["wo"]
    return out, (k, v)


def attention_decode_block(
    p: Params,
    x: jnp.ndarray,  # (B, 1, d)
    cfg: ArchConfig,
    pos: jnp.ndarray,  # (B,)
    cache: Dict[str, jnp.ndarray],
    *,
    window: int = 0,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One decode step against a ring-buffer KV cache.

    cache = {"k": (B,W,KV,D), "v": (B,W,KV,D), "pos": (B,W) int32}
    """
    B = x.shape[0]
    positions = pos[:, None]  # (B, 1)
    if cfg.mrope:
        pos3 = jnp.broadcast_to(positions[:, None, :], (B, 3, 1))
        q, k, v = _project_qkv(p, x, cfg)
        q = apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
    else:
        q, k, v = _project_qkv(p, x, cfg)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    W = cache["k"].shape[1]
    slot = (pos % W).astype(jnp.int32)  # (B,)
    bidx = jnp.arange(B)
    k_cache = cache["k"].at[bidx, slot].set(k[:, 0])
    v_cache = cache["v"].at[bidx, slot].set(v[:, 0])
    pos_cache = cache["pos"].at[bidx, slot].set(pos.astype(jnp.int32))
    out = decode_attention(q, k_cache, v_cache, pos_cache, pos, window=window)
    out = out.reshape(B, 1, -1) @ p["wo"]
    return out, {"k": k_cache, "v": v_cache, "pos": pos_cache}


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d_model, d_ff), dtype),
        "w_up": dense_init(ks[1], (d_model, d_ff), dtype),
        "w_down": dense_init(ks[2], (d_ff, d_model), dtype),
    }


def mlp_block(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE: top-k router + sort-based capacity dispatch (expert parallel)
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ArchConfig, dtype) -> Params:
    d, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 4)
    p: Params = {
        # router stays high-precision (precision-sensitive; see DESIGN §5)
        "router": dense_init(ks[0], (d, E), jnp.float32),
        "w_gate": dense_init(ks[1], (E, d, F), dtype),
        "w_up": dense_init(ks[2], (E, d, F), dtype),
        "w_down": dense_init(ks[3], (E, F, d), dtype),
    }
    if cfg.dense_residual:
        p["dense_mlp"] = init_mlp(jax.random.fold_in(key, 7), d, cfg.d_ff, dtype)
    return p


def _route_local(xf, router, E: int, K: int, capacity: int):
    """Local top-K routing + rank-within-expert. xf: (T, d).

    Returns (gate_vals (T,K), safe_expert (TK,), safe_rank (TK,),
    keep (TK,), aux).
    """
    T = xf.shape[0]
    logits = xf.astype(jnp.float32) @ router  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    # aux load-balance loss (Switch): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32),
                  axis=0)
    aux = E * jnp.sum(me * ce)

    flat_expert = expert_ids.reshape(-1)  # (TK,)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    first = jnp.searchsorted(sorted_expert, jnp.arange(E), side="left")
    rank_sorted = jnp.arange(T * K) - first[sorted_expert]
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
    keep = rank < capacity
    safe_expert = jnp.where(keep, flat_expert, 0)
    safe_rank = jnp.where(keep, rank, 0)
    return gate_vals, safe_expert, safe_rank, keep, aux


def _moe_math_local(xf, p, E: int, K: int, cap_factor: float):
    """Single-device MoE: route -> (E, C, d) buffer -> expert einsum ->
    gather+reshape combine (no scatter in the combine)."""
    T, d = xf.shape
    C = max(1, int(T * K / E * cap_factor))
    gate_vals, safe_expert, safe_rank, keep, aux = _route_local(
        xf, p["router"], E, K, C)
    tok_of = jnp.arange(T * K) // K
    buf = jnp.zeros((E, C, d), xf.dtype)
    contrib = jnp.where(keep[:, None], xf[tok_of], 0)
    buf = buf.at[safe_expert, safe_rank].add(contrib.astype(xf.dtype))
    h = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, p["w_down"])
    gathered = y[safe_expert, safe_rank]  # (TK, d)
    weighted = gathered.astype(jnp.float32) * \
        jnp.where(keep, gate_vals.reshape(-1), 0.0)[:, None]
    out = weighted.reshape(T, K, d).sum(axis=1)
    return out.astype(xf.dtype), aux


def _mesh_info():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    dp_axes = tuple(a for a in ("pod", "data") if a in sizes)
    dp = 1
    for a in dp_axes:
        dp *= sizes[a]
    return {"sizes": sizes, "dp_axes": dp_axes, "dp": dp,
            "mp": sizes.get("model", 1)}


def moe_uses_shard_map(info, E: int, K: int, T: int) -> bool:
    """Route MoE through the expert-parallel all-to-all path?

    Requires a model axis to parallelise over, divisible experts/tokens,
    and enough routed work per device to amortise gathering the local
    expert weights: decode steps route T_loc*K << E pairs, where the
    GSPMD fallback (weights stay sharded) is cheaper — measured 1.9 s vs
    5.2 s collective on kimi decode_32k (EXPERIMENTS.md §Perf iter 6).
    """
    return (
        info is not None and info["mp"] > 1 and E % info["mp"] == 0
        and T % info["dp"] == 0
        and (T // info["dp"]) * K >= E
    )


def moe_block(
    p: Params,
    x: jnp.ndarray,  # (B, S, d)
    cfg: ArchConfig,
    *,
    capacity_factor: float = 1.25,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out, aux_load_balance_loss).

    Distributed path (§Perf iter 4/5): explicit expert parallelism via
    ``shard_map`` — tokens stay on their data shard, routing/sort/scatter
    are device-local, and the dispatch/return transport is a pair of
    ``all_to_all`` collectives over the ``model`` axis (bytes ≈
    2·T_loc·K·cf·d per device per layer). Letting GSPMD partition a shared
    dispatch buffer instead was measured at 9.9 TB (single (E,C,d) buffer,
    all-reduced over data) and 89 TB (grouped (G,E,C,d) buffer, scatter
    replication) of per-step collective traffic on kimi-k2 train_4k.

    Falls back to the purely local math on a single device / indivisible
    shapes. Token overflow beyond each expert's per-source capacity is
    dropped (GShard-style; the aux loss pushes the router toward balance).
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    T = B * S
    info = _mesh_info()
    use_shard_map = moe_uses_shard_map(info, E, K, T)

    if not use_shard_map:
        out, aux = _moe_math_local(x.reshape(T, d), p, E, K, capacity_factor)
        out = out.reshape(B, S, d)
        if cfg.dense_residual:
            out = out + mlp_block(p["dense_mlp"], x)
        return out, aux

    M = info["mp"]
    dp_axes = info["dp_axes"]
    E_loc = E // M
    T_loc = T // info["dp"]
    C = max(1, int(T_loc * K / E * capacity_factor))

    def inner(router, w_gate, w_up, w_down, xf):
        # local views: xf (1..,T_loc,d); weights are this device's expert
        # slice (E_loc, d, F); router replicated.
        xf = xf.reshape(T_loc, d)
        gate_vals, safe_expert, safe_rank, keep, aux = _route_local(
            xf, router, E, K, C)
        aux = jax.lax.pmean(aux, dp_axes + ("model",))
        tok_of = jnp.arange(T_loc * K) // K
        # device-local dispatch buffer, grouped by target model-device
        send = jnp.zeros((E, C, d), xf.dtype)
        contrib = jnp.where(keep[:, None], xf[tok_of], 0)
        send = send.at[safe_expert, safe_rank].add(contrib.astype(xf.dtype))
        send = send.reshape(M, E_loc, C, d)
        # all-to-all over the model axis: row m -> model-device m;
        # received rows indexed by source device. The expert einsums keep
        # the source-device axis as a batch dim — no transposes (each
        # transpose materialised a full dispatch buffer; §Perf iter 5b).
        recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                                  tiled=True)  # (M, E_loc, C, d)
        h = jnp.einsum("mecd,edf->mecf", recv, w_gate)
        u = jnp.einsum("mecd,edf->mecf", recv, w_up)
        y = jnp.einsum("mecf,efd->mecd", jax.nn.silu(h) * u, w_down)
        got = jax.lax.all_to_all(y, "model", split_axis=0, concat_axis=0,
                                 tiled=True).reshape(E, C, d)
        gathered = got[safe_expert, safe_rank]  # (T_loc*K, d), stays bf16
        gate = jnp.where(keep, gate_vals.reshape(-1), 0.0)
        weighted = gathered * gate[:, None].astype(gathered.dtype)
        out = weighted.reshape(T_loc, K, d).sum(axis=1).astype(xf.dtype)
        return out, aux

    mesh = jax.sharding.get_abstract_mesh()

    dp_entry = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    out, aux = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(None, None),            # router (replicated)
                  P("model", None, None),   # w_gate: expert slice
                  P("model", None, None),   # w_up
                  P("model", None, None),   # w_down
                  P(dp_entry, None)),       # tokens: (T, d) over dp
        out_specs=(P(dp_entry, None), P()),
        check_vma=False,
    )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x.reshape(T, d))
    out = out.reshape(B, S, d)

    if cfg.dense_residual:
        out = out + mlp_block(p["dense_mlp"], x)
    return out, aux

"""Shared building blocks: linear (with the MMA quantized path), norms, RoPE,
flash attention (chunked online-softmax, SWA-capable), MLPs.

Params are plain pytrees (nested dicts of jnp arrays); init_* functions
build them.  Everything is functional — no module framework — so stacks can
be vmapped/scanned and sharded freely.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import mma
from repro.parallel.sharding import constrain

# Static scale for the int8 KV cache (post-RMSNorm K/V magnitudes are ~O(1);
# 0.05 gives +-6.35 dynamic range with <0.4% saturation on our smoke nets —
# a production deployment calibrates this per layer from a few batches).
KV_CACHE_SCALE = 0.05

# ---------------------------------------------------------------- init utils


def _dense_init(key, shape, in_axis=0):
    fan_in = shape[in_axis] if isinstance(in_axis, int) else math.prod(
        shape[a] for a in in_axis
    )
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32) * std).astype(
        jnp.bfloat16
    )


def init_linear(key, d_in: int, d_out: int, *, bias: bool = False) -> dict:
    p = {"w": _dense_init(key, (d_in, d_out))}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.bfloat16)
    return p


def init_norm(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.bfloat16)}


# ------------------------------------------------------------------- kernels


def linear(p: dict, x: jax.Array, quant=None) -> jax.Array:
    """Dense layer; routes through the MMA int8 bit-serial datapath when the
    config enables the paper's technique (weights per-channel int8, dynamic
    activation scale, ``planes`` MSB planes — see core/mma.py).

    ``w_q``/``w_scale`` leaves (from quant.quantize_params_int8 — serving
    mode) carry pre-quantized int8 weights: half the HBM bytes of bf16 and
    no requantization per step.
    """
    # Per-row activation scales for batched inputs: a tensor-wide amax lets
    # one batch row's magnitudes shift every other row's quantization grid,
    # breaking the slot-isolation invariant the serving engines document
    # (the fused pallas epilogue takes one scale, so that path keeps the
    # per-tensor grid).
    batch_axis = 0 if x.ndim >= 3 else None
    if "w_q" in p:
        out = mma.mma_linear_int8(
            x.astype(jnp.float32), p["w_q"], jnp.squeeze(p["w_scale"], axis=-2),
            planes=quant.planes if quant is not None else 8,
            impl=quant.impl if quant is not None else "xla",
            batch_axis=batch_axis,
        ).astype(x.dtype)
    else:
        w = p["w"]
        if quant is not None and quant.mode == "mma_int8":
            out = mma.mma_linear(
                x.astype(jnp.float32), w.astype(jnp.float32), planes=quant.planes,
                impl=quant.impl, batch_axis=batch_axis,
            ).astype(x.dtype)
        else:
            out = jax.lax.dot_general(
                x, w.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
            )
    if "b" in p:
        out = out + p["b"].astype(out.dtype)
    return out


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (math.log(theta) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def constrain_qkv(q, k, v, cfg, s):
    """Attention sharding: head-sharded (TP) when n_heads divides |model|;
    otherwise CONTEXT PARALLELISM (q seq-sharded over 'model', kv
    replicated).  Without the fallback, archs whose head counts don't divide
    the model axis (minitron 24H, whisper 20H on a 16-way axis) replicate
    all attention FLOPs |model|x — caught by the dry-run roofline
    (EXPERIMENTS.md §Perf iteration 2)."""
    from repro.parallel.sharding import current_mesh

    mesh = current_mesh()
    msize = mesh.shape.get("model", 1) if mesh is not None else 1
    heads_ok = cfg.n_heads % msize == 0
    if s <= 8:
        # Decode: k/v must match the (sequence-sharded) cache layout BEFORE
        # the dynamic-update-slice — head-sharding them forces GSPMD to
        # all-to-all the entire cache between layouts every token (zamba2
        # decode baseline: 12 GB/step of resharding a2a — §Perf).
        q = constrain(q, "batch", None, None, None)
        k = constrain(k, "batch", None, None, None)
        v = constrain(v, "batch", None, None, None)
    elif heads_ok:
        q = constrain(q, "batch", None, "heads", None)
        k = constrain(k, "batch", None, "kv_heads", None)
        v = constrain(v, "batch", None, "kv_heads", None)
    else:
        q = constrain(q, "batch", "seq", None, None)
        k = constrain(k, "batch", None, None, None)
        v = constrain(v, "batch", None, None, None)
    return q, k, v


# ----------------------------------------------------------- flash attention


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    chunk: int = 1024,
    q_offset: jax.Array | int = 0,
) -> jax.Array:
    """Chunked online-softmax attention (pure JAX, O(S*chunk) memory).

    q: (B, S, H, D); k, v: (B, T, KV, D) with H % KV == 0 (GQA).
    ``window``>0 limits attention to the last ``window`` keys (SWA).
    ``q_offset``: absolute position of q[0] (decode: T_cache).
    """
    b, s, h, d = q.shape
    _, t, kv, _ = k.shape
    groups = h // kv
    scale = 1.0 / math.sqrt(d)
    if jnp.ndim(q_offset) > 0:  # per-row offsets (slot-isolated decode)
        q_pos = jnp.reshape(q_offset, (-1, 1)) + jnp.arange(s)[None, :]
    else:
        q_pos = (jnp.arange(s) + q_offset)[None, :]  # (1, S)
    qg = q.reshape(b, s, kv, groups, d)

    # Short-query (decode) fast path: one unchunked pass — no loop, full
    # flops visible to cost_analysis, scores stay small ((B,KV,G,s,T)).
    if s <= 8:
        k_pos = jnp.arange(t)[None, :]
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
        ok = (k_pos[None, :, :] <= q_pos[..., None]) if causal else jnp.ones((1, s, t), bool)
        if window:
            ok = ok & (k_pos[None, :, :] > q_pos[..., None] - window)
        scores = jnp.where(ok[:, None, None, :, :], scores, -jnp.inf)
        m = scores.max(axis=-1, keepdims=True)
        p = jnp.exp(scores - jax.lax.stop_gradient(m))
        out = jnp.einsum("bkgst,btkd->bskgd", p.astype(q.dtype), v).astype(jnp.float32)
        out = out / jnp.maximum(p.sum(-1), 1e-20).transpose(0, 3, 1, 2)[..., None]
        return out.reshape(b, s, h, d).astype(q.dtype)

    n_chunks = (t + chunk - 1) // chunk
    tc = n_chunks * chunk
    k = jnp.pad(k, ((0, 0), (0, tc - t), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, tc - t), (0, 0), (0, 0)))

    # Online-softmax over kv chunks, UNROLLED python loop: flops fully
    # visible to the roofline (a lax.scan body is cost-counted once), and XLA
    # still schedules the chain with O(S*chunk) liveness.
    m_prev = jnp.full((b, kv, groups, s), -jnp.inf, jnp.float32)
    l_prev = jnp.zeros((b, kv, groups, s), jnp.float32)
    acc = jnp.zeros((b, s, kv, groups, d), jnp.float32)
    for j in range(n_chunks):
        kj = jax.lax.dynamic_slice_in_dim(k, j * chunk, chunk, 1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * chunk, chunk, 1)
        k_pos = (j * chunk + jnp.arange(chunk))[:, None]  # (chunk, 1)
        scores = jnp.einsum("bskgd,bckd->bkgsc", qg, kj).astype(jnp.float32) * scale
        ok = (k_pos.T <= q_pos[..., None]) if causal else jnp.ones((1, s, chunk), bool)
        if window:
            ok = ok & (k_pos.T > q_pos[..., None] - window)
        ok = ok & (k_pos[:, 0] < t)[None, None, :]
        scores = jnp.where(ok[:, None, None, :, :], scores, -jnp.inf)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)  # all-masked rows
        p = jnp.exp(scores - m_safe[..., None])
        p = jnp.where(jnp.isfinite(scores), p, 0.0)
        corr = jnp.exp(jnp.where(jnp.isfinite(m_prev), m_prev - m_safe, -jnp.inf))
        corr = jnp.where(jnp.isfinite(corr), corr, 0.0)
        l_prev = l_prev * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgsc,bckd->bskgd", p.astype(q.dtype), vj).astype(jnp.float32)
        acc = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        m_prev = m_new
    l = jnp.maximum(l_prev, 1e-20)
    out = acc / l.transpose(0, 3, 1, 2)[..., None]
    return out.reshape(b, s, h, d).astype(q.dtype)


# -------------------------------------------------------------------- blocks


def init_attention(key, cfg) -> dict:
    ks = jax.random.split(key, 4)
    hd = cfg.hd
    return {
        "wq": init_linear(ks[0], cfg.d_model, cfg.n_heads * hd),
        "wk": init_linear(ks[1], cfg.d_model, cfg.n_kv_heads * hd),
        "wv": init_linear(ks[2], cfg.d_model, cfg.n_kv_heads * hd),
        "wo": init_linear(ks[3], cfg.n_heads * hd, cfg.d_model),
    }


def attention(
    p: dict,
    x: jax.Array,
    cfg,
    *,
    positions: jax.Array,
    cache: tuple[jax.Array, jax.Array] | None = None,
    cache_index: jax.Array | None = None,
    causal: bool = True,
):
    """Multi-head attention with GQA/MQA, RoPE, SWA and optional KV cache.

    x: (B, S, D) — seq-sharded on entry (SP); internals are head-sharded.
    cache: (k, v) each (B, S_max, KV, hd); cache_index: write offset —
    a scalar (every row appends at the same position, the batched-serving
    approximation), or a (B,) vector of per-row positions (slot-isolated
    decode: each row writes at its own length, so a row's cache history
    depends only on its own tokens and serving order cannot perturb
    numerics — what the engine's chunked-prefill path relies on).
    Returns (out, new_cache).
    """
    b, s, _ = x.shape
    hd = cfg.hd
    q = linear(p["wq"], x, cfg.quant).reshape(b, s, cfg.n_heads, hd)
    k = linear(p["wk"], x, cfg.quant).reshape(b, s, cfg.n_kv_heads, hd)
    v = linear(p["wv"], x, cfg.quant).reshape(b, s, cfg.n_kv_heads, hd)
    q, k, v = constrain_qkv(q, k, v, cfg, s)
    if positions is not None:  # rope (None for whisper learned-pos)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        ck, cv = cache
        if jnp.ndim(cache_index) > 0:
            # per-row write positions: row b's update lands at its own
            # index, so rows never stomp each other's cache history
            def _write(c, u):
                return jax.vmap(
                    lambda cr, ur, i: jax.lax.dynamic_update_slice(
                        cr, ur, (i, 0, 0)
                    )
                )(c, u, cache_index)
        else:
            def _write(c, u):
                return jax.lax.dynamic_update_slice(
                    c, u, (0, cache_index, 0, 0)
                )
        if ck.dtype == jnp.int8:
            # int8 KV cache with a calibrated static scale (TRT-LLM-style;
            # halves decode cache traffic — §Perf iteration 3).
            kq = jnp.clip(jnp.round(k.astype(jnp.float32) / KV_CACHE_SCALE),
                          -127, 127).astype(jnp.int8)
            vq = jnp.clip(jnp.round(v.astype(jnp.float32) / KV_CACHE_SCALE),
                          -127, 127).astype(jnp.int8)
            ck = _write(ck, kq)
            cv = _write(cv, vq)
            new_cache = (ck, cv)
            k = (ck.astype(jnp.float32) * KV_CACHE_SCALE).astype(q.dtype)
            v = (cv.astype(jnp.float32) * KV_CACHE_SCALE).astype(q.dtype)
        else:
            ck = _write(ck, k.astype(ck.dtype))
            cv = _write(cv, v.astype(cv.dtype))
            new_cache = (ck, cv)
            k, v = ck, cv
        q_offset = cache_index
        if s <= 8:
            # Decode: keep the cache sequence-sharded ('kv_seq' -> model) and
            # replicate the tiny q heads — attention becomes a partial
            # softmax per seq shard + an O(B*H*d) psum instead of an
            # all-gather of the cache (see EXPERIMENTS.md SPerf).
            q = constrain(q, "batch", None, None, None)
            k = constrain(k, "batch", "kv_seq", None, None)
            v = constrain(v, "batch", "kv_seq", None, None)
    else:
        q_offset = 0

    out = flash_attention(
        q, k, v, causal=causal, window=cfg.swa_window, chunk=cfg.attn_chunk,
        q_offset=q_offset,
    )
    out = constrain(out, "batch", None, "heads", None)
    out = linear(p["wo"], out.reshape(b, s, cfg.n_heads * hd), cfg.quant)
    return out, new_cache


def init_mlp(key, cfg, d_ff: int | None = None) -> dict:
    ks = jax.random.split(key, 3)
    ff = d_ff or cfg.d_ff
    if cfg.act == "swiglu":
        return {
            "w_gate": init_linear(ks[0], cfg.d_model, ff),
            "w_up": init_linear(ks[1], cfg.d_model, ff),
            "w_down": init_linear(ks[2], ff, cfg.d_model),
        }
    return {
        "w_up": init_linear(ks[0], cfg.d_model, ff, bias=True),
        "w_down": init_linear(ks[1], ff, cfg.d_model, bias=True),
    }


def mlp(p: dict, x: jax.Array, cfg) -> jax.Array:
    if "w_gate" in p:
        g = linear(p["w_gate"], x, cfg.quant)
        u = linear(p["w_up"], x, cfg.quant)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        h = jax.nn.gelu(linear(p["w_up"], x, cfg.quant).astype(jnp.float32)).astype(
            x.dtype
        )
    h = constrain(h, "batch", None, "ffn")
    return linear(p["w_down"], h, cfg.quant)


def init_embedding(key, vocab: int, d: int) -> dict:
    return {"table": (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(jnp.bfloat16)}


def embed(p: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


def unembed(p: dict, x: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        x, p["table"].astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ()))
    )

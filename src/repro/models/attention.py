"""Attention: GQA projections + three execution paths.

* :func:`flash_attention` — block-chunked online-softmax over KV blocks
  (training / global-attention prefill). O(S·block) memory instead of O(S²);
  the Pallas kernel (`repro.kernels.attention`) implements the same schedule
  for real TPUs and is validated against `kernels/attention/ref.py`.
* :func:`local_attention` — sliding-window attention with a *sequential scan
  over query blocks* and statically-sized KV windows: O(S·W) compute and
  O(B·bq·W) memory, which is what makes `long_500k` lowerable for the
  hybrid archs.
* :func:`decode_attention` — one query step against a cache.

All softmax arithmetic is fp32 regardless of compute dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import get_abstract_mesh

from ..configs.base import ModelConfig
from .layers import (
    EMBED, HEADDIM, KVHEADS, QHEADS,
    ParamSpec, apply_rope, constrain_bshd, qk_norm, softcap,
)

NEG_INF = -2.0e38


# --------------------------------------------------------------------------- specs


def attn_specs(cfg: ModelConfig, cross: bool = False) -> dict[str, ParamSpec]:
    d, h, hq, hkv = cfg.d_model, cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
    specs = {
        "wq": ParamSpec((d, hq, h), (EMBED, QHEADS, HEADDIM)),
        "wk": ParamSpec((d, hkv, h), (EMBED, KVHEADS, HEADDIM)),
        "wv": ParamSpec((d, hkv, h), (EMBED, KVHEADS, HEADDIM)),
        "wo": ParamSpec((hq, h, d), (QHEADS, HEADDIM, EMBED)),
    }
    if cfg.qk_norm and not cross:
        specs["q_gamma"] = ParamSpec((h,), (HEADDIM,), init="zeros")
        specs["k_gamma"] = ParamSpec((h,), (HEADDIM,), init="zeros")
    return specs


def project_q(params, x, cfg: ModelConfig, positions, *, rope: bool = True):
    q = constrain_bshd(jnp.einsum("bsd,dhk->bshk", x, params["wq"]))
    if cfg.qk_norm and "q_gamma" in params:
        q = qk_norm(q, params["q_gamma"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, theta=cfg.rope_theta, mode=cfg.rope_mode,
                       sections=cfg.mrope_sections)
    return q


def project_kv(params, x, cfg: ModelConfig, positions, *, rope: bool = True):
    k = constrain_bshd(jnp.einsum("bsd,dhk->bshk", x, params["wk"]))
    v = constrain_bshd(jnp.einsum("bsd,dhk->bshk", x, params["wv"]))
    if cfg.qk_norm and "k_gamma" in params:
        k = qk_norm(k, params["k_gamma"], cfg.norm_eps)
    if rope:
        k = apply_rope(k, positions, theta=cfg.rope_theta, mode=cfg.rope_mode,
                       sections=cfg.mrope_sections)
    return k, v


def o_proj(params, ctx):
    return jnp.einsum("bshk,hkd->bsd", ctx, params["wo"])


# --------------------------------------------------------------------------- helpers


def _expand_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B,S,Hkv,D) -> (B,S,Hq,D) by repeating each KV head `groups` times.

    GQA via head-repeat instead of a (Hkv, G) q-reshape: with Hq sharded
    over 'model', the reshape (32 heads/16 shards -> (8,4)) cannot preserve
    the sharding and XLA falls back to "involuntary full rematerialization"
    (replicate + repartition). Repeating KV keeps every tensor sharded on
    the same Hq axis; the repeat itself is free on the sharded dim.
    """
    if groups == 1:
        return k
    return constrain_bshd(jnp.repeat(k, groups, axis=2))


def _scale(head_dim: int) -> float:
    return 1.0 / np.sqrt(head_dim)


# --------------------------------------------------------------------------- flash (kv-block scan)
#
# custom_vjp: without it, jax's AD of the kv-block scan stores every block's
# probability matrix — i.e. the full (B,H,Sq,Skv) fp32 scores — which is
# exactly the O(S^2) memory flash attention exists to avoid (4 GiB/layer/
# device at train_4k; impossible at 32k). The flash backward recomputes
# p per block from the saved (out, lse) pair: ~30% more attention FLOPs for
# O(S·block) memory — the standard trade (FlashAttention, arXiv:2205.14135).


def _mask_for(q_idx, k_idx, causal: bool, window: int, skv: int):
    mask = k_idx[None, :] < skv
    if causal:
        mask = mask & (q_idx[:, None] >= k_idx[None, :])
    if window > 0:
        mask = mask & (q_idx[:, None] - k_idx[None, :] < window)
    return mask


def _blockify(x: jax.Array, bkv: int):
    b, skv, h, d = x.shape
    pad = (-skv) % bkv
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n = (skv + pad) // bkv
    return x.reshape(b, n, bkv, h, d).transpose(1, 0, 2, 3, 4), n


def _flash_fwd_scan(qf, kb, vb, nkv, bkv, q_idx, skv, causal, window, cap):
    b, sq, hq, d = qf.shape[0], qf.shape[1], qf.shape[2], qf.shape[3]

    def body(carry, inputs):
        m, l, acc = carry
        j, kj, vj = inputs
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kj.astype(jnp.float32))
        if cap > 0:
            s = softcap(s, cap)
        k_idx = j * bkv + jnp.arange(bkv)
        s = jnp.where(_mask_for(q_idx, k_idx, causal, window, skv)[None, None],
                      s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vj.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    a0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (jnp.arange(nkv), kb, vb))
    l = jnp.maximum(l, 1e-37)
    out = acc / l[..., None]                       # (B,H,Sq,D) fp32
    lse = m + jnp.log(l)                           # (B,H,Sq)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(qf, k, v, causal, window, q_offset, block_kv, cap):
    """(B,Sq,Hq,D) fp32-scaled q; k/v already expanded to Hq heads."""
    b, sq, hq, d = qf.shape
    skv = k.shape[1]
    bkv = min(block_kv, skv)
    kb, nkv = _blockify(k, bkv)
    vb, _ = _blockify(v, bkv)
    q_idx = q_offset + jnp.arange(sq)
    out, _ = _flash_fwd_scan(qf, kb, vb, nkv, bkv, q_idx, skv, causal, window, cap)
    return out.transpose(0, 2, 1, 3)               # (B,Sq,Hq,D) fp32


def _flash_core_fwd(qf, k, v, causal, window, q_offset, block_kv, cap):
    b, sq, hq, d = qf.shape
    skv = k.shape[1]
    bkv = min(block_kv, skv)
    kb, nkv = _blockify(k, bkv)
    vb, _ = _blockify(v, bkv)
    q_idx = q_offset + jnp.arange(sq)
    out, lse = _flash_fwd_scan(qf, kb, vb, nkv, bkv, q_idx, skv, causal, window, cap)
    return out.transpose(0, 2, 1, 3), (qf, k, v, out, lse)


def _flash_core_bwd(causal, window, q_offset, block_kv, cap, res, g):
    qf, k, v, out, lse = res                       # out (B,H,Sq,D) fp32
    b, sq, hq, d = qf.shape
    skv = k.shape[1]
    bkv = min(block_kv, skv)
    kb, nkv = _blockify(k, bkv)
    vb, _ = _blockify(v, bkv)
    q_idx = q_offset + jnp.arange(sq)
    gf = g.astype(jnp.float32).transpose(0, 2, 1, 3)          # (B,H,Sq,D)
    delta = jnp.sum(gf * out, axis=-1)                        # (B,H,Sq)

    def body(dq, inputs):
        j, kj, vj = inputs
        kjf, vjf = kj.astype(jnp.float32), vj.astype(jnp.float32)
        u = jnp.einsum("bqhd,bkhd->bhqk", qf, kjf)            # pre-cap scores
        s = softcap(u, cap) if cap > 0 else u
        k_idx = j * bkv + jnp.arange(bkv)
        mask = _mask_for(q_idx, k_idx, causal, window, skv)
        s = jnp.where(mask[None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                       # (B,H,Sq,bkv)
        dv_j = jnp.einsum("bhqk,bhqd->bkhd", p, gf)
        dp = jnp.einsum("bhqd,bkhd->bhqk", gf, vjf)
        ds = p * (dp - delta[..., None])
        if cap > 0:
            ds = ds * (1.0 - jnp.square(jnp.tanh(u / cap)))   # d softcap/du
        ds = jnp.where(mask[None, None], ds, 0.0)
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds, kjf)
        dk_j = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros_like(qf)
    dq, (dkb, dvb) = jax.lax.scan(body, dq0, (jnp.arange(nkv), kb, vb))
    dk = dkb.transpose(1, 0, 2, 3, 4).reshape(b, nkv * bkv, hq, d)[:, :skv]
    dv = dvb.transpose(1, 0, 2, 3, 4).reshape(b, nkv * bkv, hq, d)[:, :skv]
    return dq.astype(qf.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_kv", "attn_softcap", "q_offset"),
)
def flash_attention(
    q: jax.Array,                # (B, Sq, Hq, D)
    k: jax.Array,                # (B, Skv, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,             # 0 => unbounded
    q_offset: int = 0,           # global index of q row 0 (chunked prefill)
    block_kv: int = 512,
    attn_softcap: float = 0.0,
) -> jax.Array:
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    k = _expand_kv(k, hq // hkv)
    v = _expand_kv(v, hq // hkv)
    qf = q.astype(jnp.float32) * _scale(d)
    out = _flash_core(qf, k, v, causal, window, q_offset, block_kv,
                      attn_softcap)
    return out.astype(q.dtype)


# --------------------------------------------------------------------------- local (q-block scan)


@functools.partial(
    jax.jit, static_argnames=("window", "block_q", "attn_softcap", "q_offset")
)
def local_attention(
    q: jax.Array,                # (B, S, Hq, D)
    k: jax.Array,                # (B, S, Hkv, D)
    v: jax.Array,
    *,
    window: int,
    q_offset: int = 0,
    block_q: int = 512,
    attn_softcap: float = 0.0,
) -> jax.Array:
    """Causal sliding-window attention, O(S·window)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    k = _expand_kv(k, hq // hkv)
    v = _expand_kv(v, hq // hkv)
    bq = min(block_q, s)
    pad_q = (-s) % bq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    nq = (s + pad_q) // bq
    w = min(window, s)  # clamp: window can exceed sequence
    span = w + bq       # kv needed per q block

    k_pad = jnp.pad(k, ((0, 0), (w, pad_q), (0, 0), (0, 0)))
    v_pad = jnp.pad(v, ((0, 0), (w, pad_q), (0, 0), (0, 0)))
    qb = q.reshape(b, nq, bq, hq, d).transpose(1, 0, 2, 3, 4)

    def body(_, inputs):
        i, qi = inputs
        start = i * bq  # into padded kv: covers original [start-w, start+bq)
        kw = jax.lax.dynamic_slice_in_dim(k_pad, start, span, axis=1)
        vw = jax.lax.dynamic_slice_in_dim(v_pad, start, span, axis=1)
        sc = jnp.einsum(
            "bqhd,bkhd->bhqk",
            qi.astype(jnp.float32) * _scale(d),
            kw.astype(jnp.float32),
        )
        if attn_softcap > 0:
            sc = softcap(sc, attn_softcap)
        q_idx = q_offset + start + jnp.arange(bq)
        k_idx = start - w + jnp.arange(span) + q_offset
        mask = (
            (q_idx[:, None] >= k_idx[None, :])
            & (q_idx[:, None] - k_idx[None, :] < w)
            & (k_idx[None, :] >= q_offset)
            & (q_idx[:, None] < q_offset + s)
        )
        sc = jnp.where(mask[None, None], sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, vw.astype(jnp.float32))
        return None, out

    _, outs = jax.lax.scan(body, None, (jnp.arange(nq), qb))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * bq, hq, d)
    return out[:, :s].astype(q.dtype)


# --------------------------------------------------------------------------- decode


def decode_attention(
    q: jax.Array,                # (B, 1, Hq, D)
    k_cache: jax.Array,          # (B, Smax, Hkv, D)
    v_cache: jax.Array,
    cache_len: jax.Array,        # scalar int32: #valid cache rows (incl. this step)
    *,
    window: int = 0,
    attn_softcap: float = 0.0,
) -> jax.Array:
    b, _, hq, d = q.shape
    hkv = k_cache.shape[2]
    g = hq // hkv
    qf = q.astype(jnp.float32) * _scale(d)
    # decode keeps the grouped einsum: the cache stays (B,S,Hkv,D) with its
    # *sequence* dim model-sharded (split-KV decode), so no head reshapes
    # of sharded dims occur here.
    qg = qf.reshape(b, 1, hkv, g, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache.astype(jnp.float32))
    if attn_softcap > 0:
        s = softcap(s, attn_softcap)
    k_idx = jnp.arange(k_cache.shape[1])
    mask = k_idx < cache_len
    if window > 0:
        mask &= k_idx >= cache_len - window
    s = jnp.where(mask[None, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, hq, d).astype(q.dtype)


# --------------------------------------------------------------------------- int8 KV cache

def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(token, head) symmetric int8. x: (..., S, H, D) ->
    (int8 same shape, fp16-ish scale (..., S, H, 1))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale.astype(jnp.float32)


def _cache_is_int8(cache: dict) -> bool:
    return "k_scale" in cache


# --------------------------------------------------------------------------- split-KV decode (shard_map)


def _split_kv_available(cache_k: jax.Array) -> bool:
    """True when the ambient mesh has a 'model' axis that divides the cache
    sequence dim — the split-KV decode layout (flash-decoding on the mesh)."""
    mesh = get_abstract_mesh()
    if mesh is None or "model" not in mesh.shape:
        return False
    n = mesh.shape["model"]
    return cache_k.shape[1] % n == 0 and cache_k.shape[1] >= n


def decode_step_split_kv(
    q: jax.Array,                # (B, 1, Hq, D)
    k_new: jax.Array,            # (B, 1, Hkv, D)
    v_new: jax.Array,
    cache: dict,                 # k/v (B, Smax, Hkv, D), seq sharded 'model'
    cache_len: jax.Array,
    *,
    window: int = 0,
    attn_softcap: float = 0.0,
) -> tuple[jax.Array, dict]:
    """One decode step with the KV ring sharded over 'model' by *sequence*.

    Naive GSPMD handling of a dynamic-update-slice into a seq-sharded ring
    reshards/gathers the whole cache every step (tens of GB per token at
    32k/128). Here each model shard owns a seq stripe: the owning shard
    writes the new token locally, every shard computes partial (max, sum,
    out) over its stripe, and three tiny psums ((B,H)-sized) combine them —
    the flash-decoding split-KV schedule expressed on the mesh. Batch stays
    auto-sharded over ('pod','data') (partial-manual shard_map).
    """
    mesh = get_abstract_mesh()
    n = mesh.shape["model"]
    smax = cache["k"].shape[1]
    s_loc = smax // n
    P = jax.sharding.PartitionSpec
    cache_spec = P(None, "model", None, None)
    int8 = _cache_is_int8(cache)

    def upd(buf, new, tgt_in_range, safe):
        buf2 = jax.lax.dynamic_update_slice_in_dim(buf, new.astype(buf.dtype),
                                                   safe, axis=1)
        return jnp.where(tgt_in_range, buf2, buf)

    def local(q, kn, vn, kc, vc, ks, vs, clen):
        b, _, hq, d = q.shape
        hkv = kc.shape[2]
        g = hq // hkv
        shard = jax.lax.axis_index("model")
        start = shard * s_loc
        tgt = (clen - 1) - start
        in_range = (tgt >= 0) & (tgt < s_loc)
        safe = jnp.clip(tgt, 0, s_loc - 1)
        if int8:
            knq, kns = quantize_kv(kn)
            vnq, vns = quantize_kv(vn)
            kc = upd(kc, knq, in_range, safe)
            vc = upd(vc, vnq, in_range, safe)
            ks = upd(ks, kns, in_range, safe)
            vs = upd(vs, vns, in_range, safe)
            kf = dequantize_kv(kc, ks)
            vf = dequantize_kv(vc, vs)
        else:
            kc = upd(kc, kn, in_range, safe)
            vc = upd(vc, vn, in_range, safe)
            kf = kc.astype(jnp.float32)
            vf = vc.astype(jnp.float32)

        qg = q.astype(jnp.float32).reshape(b, 1, hkv, g, d) * _scale(d)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf)
        if attn_softcap > 0:
            s = softcap(s, attn_softcap)
        k_idx = start + jnp.arange(s_loc)
        mask = k_idx < clen
        if window > 0:
            mask &= k_idx >= clen - window
        s = jnp.where(mask[None, None, None, None], s, NEG_INF)
        m = jax.lax.pmax(s.max(axis=-1), "model")
        p = jnp.exp(s - m[..., None])
        l = jax.lax.psum(p.sum(axis=-1), "model")
        o = jax.lax.psum(jnp.einsum("bhgqk,bkhd->bqhgd", p, vf), "model")
        out = (o / jnp.maximum(l, 1e-37).transpose(0, 3, 1, 2)[..., None])
        return out.reshape(b, 1, hq, d).astype(q.dtype), kc, vc, ks, vs

    ks = cache.get("k_scale")
    vs = cache.get("v_scale")
    if ks is None:  # placeholders so the shard_map signature is static
        ks = jnp.zeros((cache["k"].shape[0], smax, cache["k"].shape[2], 1),
                       jnp.bfloat16)
        vs = ks
    out, kc, vc, ks, vs = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), P(), cache_spec, cache_spec, cache_spec,
                  cache_spec, P()),
        out_specs=(P(), cache_spec, cache_spec, cache_spec, cache_spec),
        axis_names={"model"},
        check_vma=False,
    )(q, k_new, v_new, cache["k"], cache["v"], ks, vs, cache_len)
    new_cache = {"k": kc, "v": vc}
    if int8:
        new_cache["k_scale"] = ks
        new_cache["v_scale"] = vs
    return out, new_cache


# --------------------------------------------------------------------------- block-level API


def attention_sequence(
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: ModelConfig,
    *,
    local: bool,
    causal: bool = True,
    kv_override: Optional[tuple[jax.Array, jax.Array]] = None,
    rope: bool = True,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Full-sequence attention (train / prefill). Returns (out, (k, v))."""
    q = project_q(params, x, cfg, positions, rope=rope)
    if kv_override is not None:
        k, v = kv_override
    else:
        k, v = project_kv(params, x, cfg, positions, rope=rope)
    if local:
        ctx = local_attention(
            q, k, v, window=cfg.window, attn_softcap=cfg.attn_logit_softcap
        )
    else:
        ctx = flash_attention(
            q, k, v, causal=causal, attn_softcap=cfg.attn_logit_softcap
        )
    return o_proj(params, ctx), (k, v)


def attention_step(
    params: dict,
    x: jax.Array,                 # (B, 1, D)
    position: jax.Array,          # (B, 1) or (3, B, 1) for mrope
    cache: dict,                  # {"k": (B,Smax,Hkv,D), "v": ...}
    cache_len: jax.Array,         # valid rows AFTER this token is appended
    cfg: ModelConfig,
    *,
    local: bool,
    cross: bool = False,
) -> tuple[jax.Array, dict]:
    """Single decode step; returns (out, updated cache)."""
    q = project_q(params, x, cfg, position, rope=not cross)
    if cross:
        k_cache, v_cache = cache["k"], cache["v"]
        new_cache = cache
        valid = jnp.asarray(k_cache.shape[1], jnp.int32)
        window = 0
    else:
        k, v = project_kv(params, x, cfg, position, rope=True)
        window = cfg.window if local else 0
        if _split_kv_available(cache["k"]):
            ctx, new_cache = decode_step_split_kv(
                q, k, v, cache, cache_len,
                window=window, attn_softcap=cfg.attn_logit_softcap,
            )
            return o_proj(params, ctx), new_cache
        idx = cache_len - 1
        if _cache_is_int8(cache):
            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, idx, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, idx, axis=1)
            kss = jax.lax.dynamic_update_slice_in_dim(cache["k_scale"], ksc, idx, axis=1)
            vss = jax.lax.dynamic_update_slice_in_dim(cache["v_scale"], vsc, idx, axis=1)
            new_cache = {"k": kc, "v": vc, "k_scale": kss, "v_scale": vss}
            k_cache = dequantize_kv(kc, kss).astype(k.dtype)
            v_cache = dequantize_kv(vc, vss).astype(v.dtype)
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, idx, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, idx, axis=1)
            new_cache = {"k": k_cache, "v": v_cache}
        valid = cache_len
    ctx = decode_attention(
        q, k_cache, v_cache, valid,
        window=window, attn_softcap=cfg.attn_logit_softcap,
    )
    return o_proj(params, ctx), new_cache

"""Transformer building blocks: norms, RoPE/M-RoPE, GQA attention (flash-
style chunked for long prefill), SwiGLU MLP, capacity-based MoE with expert
parallelism, and the FFT-convolution mixer (the paper's technique as a
sequence mixer)."""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .config import ArchConfig
from .params import ParamMeta, shard_act


def _reduce_pe(cfg: ArchConfig):
    """preferred_element_type for TP-psum-carrying out-projections: the
    cross-chip all-reduce happens in this dtype.  Train keeps f32 partial
    sums (explicit — jnp.einsum would otherwise emit an f32 accumulator
    anyway); serving opts into bf16, halving reduction wire bytes."""
    return jnp.dtype(cfg.reduce_dtype) if cfg.reduce_dtype else jnp.float32

# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def norm_meta(cfg: ArchConfig) -> Dict[str, ParamMeta]:
    d = cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": ParamMeta((d,), (None,), init="ones")}
    if cfg.norm == "layernorm":
        return {"scale": ParamMeta((d,), (None,), init="ones"),
                "bias": ParamMeta((d,), (None,), init="zeros")}
    return {}  # nonparam_ln (olmo): no learnable parameters


def apply_norm(p: Dict, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + 1e-6) * p["scale"].astype(jnp.float32)
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + 1e-5)
        if cfg.norm == "layernorm":
            y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def _rope_angles(positions: jax.Array, hd: int, theta: float = 1e4) -> Tuple:
    """positions (..., S) -> cos/sin (..., S, hd//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def _mrope_angles(positions3: jax.Array, hd: int,
                  sections: Tuple[int, ...], theta: float = 1e4) -> Tuple:
    """M-RoPE (qwen2-vl): positions3 (3, B, S); per-section angle source.

    sections give the number of frequency slots (out of hd//2) driven by the
    temporal / height / width position streams respectively.
    """
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions3[..., None].astype(jnp.float32) * inv      # (3, B, S, hd/2)
    idx = []
    for sec_id, n in enumerate(sections):
        idx += [sec_id] * n
    sel = jnp.asarray(np.array(idx, np.int32))                 # (hd/2,)
    ang = jnp.take_along_axis(
        jnp.moveaxis(ang, 0, -1), sel[None, None, :, None], axis=-1)[..., 0]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (B, S, H, hd); cos/sin (B, S, hd//2)."""
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def rope_tables(cfg: ArchConfig, positions: jax.Array):
    if cfg.rope == "none":
        return None
    if cfg.rope == "mrope":
        if positions.ndim == 2:                                 # text-only: t=h=w
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return _mrope_angles(positions, cfg.hd, cfg.mrope_sections)
    return _rope_angles(positions, cfg.hd)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def attention_meta(cfg: ArchConfig) -> Dict[str, ParamMeta]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    m = {
        "wq": ParamMeta((d, h, hd), ("fsdp", "tp", None)),
        "wk": ParamMeta((d, kv, hd), ("fsdp", "tp", None)),
        "wv": ParamMeta((d, kv, hd), ("fsdp", "tp", None)),
        "wo": ParamMeta((h, hd, d), ("tp", None, "fsdp")),
    }
    if cfg.qkv_bias:
        m["bq"] = ParamMeta((h, hd), ("tp", None), init="zeros")
        m["bk"] = ParamMeta((kv, hd), ("tp", None), init="zeros")
        m["bv"] = ParamMeta((kv, hd), ("tp", None), init="zeros")
    return m


def _qkv(p: Dict, cfg: ArchConfig, x: jax.Array, rope) -> Tuple:
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_kv: int = 1024,
                    q_offset: int = 0) -> jax.Array:
    """Chunked online-softmax attention (pure JAX flash).

    q (B, Sq, H, hd); k/v (B, Sk, KV, hd) with H = KV * G.  Memory is
    O(Sq * block_kv) instead of O(Sq * Sk) — required for 32k prefill.
    """
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)
    qr = q.reshape(b, sq, kvh, g, hd).astype(jnp.float32) * scale

    block_kv = min(block_kv, sk)
    while sk % block_kv:
        block_kv -= 1
    nkv = sk // block_kv
    kb = jnp.moveaxis(k.reshape(b, nkv, block_kv, kvh, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nkv, block_kv, kvh, hd), 1, 0)

    q_pos = q_offset + jnp.arange(sq)

    def step(carry, inp):
        acc, m_run, l_run = carry
        kj, vj, j = inp
        s = jnp.einsum("bqkgd,bskd->bqkgs", qr.astype(kj.dtype), kj,
                       preferred_element_type=jnp.float32)
        if causal:
            kv_pos = j * block_kv + jnp.arange(block_kv)
            mask = q_pos[:, None] >= kv_pos[None, :]
            s = jnp.where(mask[None, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        pexp = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_run - m_new)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqkgs,bskd->bqkgd", pexp.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32)
        l_run = l_run * corr + jnp.sum(pexp, axis=-1)
        return (acc, m_new, l_run), None

    acc0 = jnp.zeros((b, sq, kvh, g, hd), jnp.float32)
    m0 = jnp.full((b, sq, kvh, g), -1e30, jnp.float32)
    l0 = jnp.zeros((b, sq, kvh, g), jnp.float32)
    (acc, _, l_run), _ = jax.lax.scan(
        step, (acc0, m0, l0), (kb, vb, jnp.arange(nkv)))
    out = acc / jnp.maximum(l_run[..., None], 1e-30)
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array) -> jax.Array:
    """Single-token attention against a (B, S, KV, hd) cache."""
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k_cache.shape
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)
    qr = (q.reshape(b, sq, kvh, g, hd) * scale).astype(k_cache.dtype)
    s = jnp.einsum("bqkgd,bskd->bqkgs", qr, k_cache,
                   preferred_element_type=jnp.float32)
    mask = jnp.arange(sk)[None, :] < cache_len[:, None]         # (B, S)
    s = jnp.where(mask[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkgs,bskd->bqkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def attention_fwd(p: Dict, cfg: ArchConfig, x: jax.Array, positions: jax.Array,
                  cache: Optional[Dict] = None) -> Tuple[jax.Array, Optional[Dict]]:
    """Returns (output, updated_cache). cache=None -> causal self-attention."""
    rope = rope_tables(cfg, positions)
    q, k, v = _qkv(p, cfg, x, rope)
    q = shard_act(q, "dp", None, "tp", None)
    if cache is None:
        out = flash_attention(q, k, v, causal=True)
    else:
        idx = cache["len"]                                      # (B,) int32
        kc = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u.astype(c.dtype), (i, 0, 0)))(cache["k"], k, idx)
        vc = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u.astype(c.dtype), (i, 0, 0)))(cache["v"], v, idx)
        out = decode_attention(q, kc, vc, idx + 1)
        cache = {"k": kc, "v": vc, "len": idx + 1}
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype),
                   preferred_element_type=_reduce_pe(cfg))
    return shard_act(y.astype(x.dtype), "dp", None, None), cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------


def mlp_meta(cfg: ArchConfig) -> Dict[str, ParamMeta]:
    d, f = cfg.d_model, cfg.d_ff
    m = {"w_up": ParamMeta((d, f), ("fsdp", "tp")),
         "w_down": ParamMeta((f, d), ("tp", "fsdp"))}
    if cfg.mlp_act == "silu":
        m["w_gate"] = ParamMeta((d, f), ("fsdp", "tp"))
    return m


def mlp_fwd(p: Dict, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    dt = x.dtype
    up = x @ p["w_up"].astype(dt)
    if cfg.mlp_act == "silu":
        up = jax.nn.silu(x @ p["w_gate"].astype(dt)) * up
    else:
        up = jax.nn.gelu(up)
    up = shard_act(up, "dp", None, "tp")
    down = jax.lax.dot_general(up, p["w_down"].astype(dt),
                               (((up.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=_reduce_pe(cfg))
    return shard_act(down.astype(dt), "dp", None, None)


# ---------------------------------------------------------------------------
# MoE: top-k routing, capacity dispatch, expert parallelism over "expert"
# ---------------------------------------------------------------------------


def moe_meta(cfg: ArchConfig) -> Dict[str, ParamMeta]:
    """Expert weights use the 'moe_d'/'moe_f' logical axes so the rule table
    can switch between training layout (d FSDP-sharded, gathered on use) and
    weight-stationary serving layout (d_ff sharded over the data axis; the
    contraction psums activations instead of all-gathering 3*d*ff*E weight
    bytes per layer — the dbrx prefill hillclimb, EXPERIMENTS.md §Perf)."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    return {
        "router": ParamMeta((d, e), (None, None), scale=0.02 / math.sqrt(d)),
        "w_up": ParamMeta((e, d, f), ("expert", "moe_d", "moe_f")),
        "w_gate": ParamMeta((e, d, f), ("expert", "moe_d", "moe_f")),
        "w_down": ParamMeta((e, f, d), ("expert", "moe_f", "moe_d")),
    }


def _group_dispatch(xg: jax.Array, eid: jax.Array, pos: jax.Array,
                    keep: jax.Array, e: int, cap: int) -> jax.Array:
    """Scatter one group's tokens into (E, cap, d) expert buffers."""
    d = xg.shape[-1]
    tk = eid.shape[0]
    buf = jnp.zeros((e, cap, d), xg.dtype)
    safe_pos = jnp.where(keep, pos, cap - 1)
    upd = xg * keep[:, None].astype(xg.dtype)
    return buf.at[eid, safe_pos].add(upd, mode="drop")


def moe_fwd(p: Dict, cfg: ArchConfig, x: jax.Array,
            num_groups: int = 1) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, d) -> (out, aux_loss). GShard-style grouped dispatch:

    tokens are grouped by data shard; dispatch buffers are laid out
    (G, E, C, d) and resharded to (E, G, C, d) — GSPMD lowers that logical
    transpose to the all_to_all the paper's communication step prescribes.
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    t = b * s
    g = num_groups
    while t % g:
        g -= 1
    tg = t // g
    cap = max(int(cfg.capacity_factor * tg * k / e), 4)
    cap = min(cap, tg * k)

    xt = x.reshape(g, tg, d)
    xt = shard_act(xt, "dp", None, None)
    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)                     # (G, Tg, E)
    topv, topi = jax.lax.top_k(gates, k)                        # (G, Tg, K)
    topv = topv / jnp.maximum(jnp.sum(topv, -1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    me = jnp.mean(gates, axis=1)                                # (G, E)
    ce = jnp.mean(jax.nn.one_hot(topi[..., 0], e, dtype=jnp.float32), axis=1)
    aux = e * jnp.mean(jnp.sum(me * ce, axis=-1))

    # position of each (token, choice) within its expert's buffer
    oh = jax.nn.one_hot(topi, e, dtype=jnp.int32)               # (G, Tg, K, E)
    flat = oh.reshape(g, tg * k, e)
    pos_all = jnp.cumsum(flat, axis=1) - flat
    pos = jnp.sum(pos_all * flat, axis=-1)                      # (G, Tg*K)
    eid = topi.reshape(g, tg * k)
    keep = pos < cap

    xrep = jnp.repeat(xt, k, axis=1)                            # (G, Tg*K, d)
    buf = jax.vmap(functools.partial(_group_dispatch, e=e, cap=cap))(
        xrep, eid, pos, keep)                                   # (G, E, C, d)
    buf = shard_act(buf, "dp", "expert", None, None)

    # expert-major layout: GSPMD inserts the all_to_all here
    ebuf = shard_act(jnp.swapaxes(buf, 0, 1), "expert", "dp", None, None)
    dt = x.dtype
    h = jnp.einsum("egcd,edf->egcf", ebuf.astype(dt), p["w_up"].astype(dt))
    hg = jnp.einsum("egcd,edf->egcf", ebuf.astype(dt), p["w_gate"].astype(dt))
    h = jax.nn.silu(hg) * h
    h = shard_act(h, "expert", "dp", None, "moe_f")
    eout = jnp.einsum("egcf,efd->egcd", h, p["w_down"].astype(dt),
                      preferred_element_type=_reduce_pe(cfg)).astype(dt)
    eout = shard_act(eout, "expert", "dp", None, None)

    # back to group-major; the buffer is REPLICATED across the expert axis
    # before the combine gather: one bf16 all-gather instead of the masked
    # gather + f32 all-reduce GSPMD otherwise emits (4x the wire bytes) —
    # see EXPERIMENTS.md §Perf (dbrx prefill iteration 2)
    gbuf = shard_act(jnp.swapaxes(eout, 0, 1), "dp", None, None, None)

    def gather_group(gb, ei, ps, kp, tv):
        got = gb[ei, ps] * kp[:, None].astype(gb.dtype)         # (Tg*K, d)
        got = got.reshape(tg, k, d) * tv[..., None].astype(gb.dtype)
        return jnp.sum(got, axis=1)

    out = jax.vmap(gather_group)(gbuf, eid, jnp.where(keep, pos, 0),
                                 keep, topv)
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# FFT-convolution mixer (paper technique in the LM stack)
# ---------------------------------------------------------------------------


def fftconv_meta(cfg: ArchConfig) -> Dict[str, ParamMeta]:
    d, r = cfg.d_model, cfg.fftconv_rank
    return {
        "w_in": ParamMeta((d, 2 * d), ("fsdp", "tp")),
        "filt": ParamMeta((d, r), (None, None), scale=0.2),
        "skip": ParamMeta((d,), (None,), init="ones"),
        "w_out": ParamMeta((d, d), ("tp", "fsdp")),
    }


def fftconv_fwd(p: Dict, cfg: ArchConfig, x: jax.Array,
                seq_axis_sharded: bool = False) -> jax.Array:
    """Gated long convolution: y = W_out( fftconv(v) * silu(g) ).

    Sub-quadratic sequence mixing powered by repro.core — the paper's FFT as
    a first-class model feature.  When the sequence is sharded, the
    distributed slab FFT (all_to_all collectives) is used.
    """
    from repro.core import fftconv as fc
    from repro.models.params import current_mesh, current_rules

    dt = x.dtype
    b, s, d = x.shape
    vg = x @ p["w_in"].astype(dt)
    v, gate = jnp.split(vg, 2, axis=-1)
    filt = fc.materialize_filter(p["filt"].astype(jnp.float32), s)
    mesh = current_mesh()
    if seq_axis_sharded and mesh is not None:
        rules = current_rules()
        axis = rules.get("sp", rules.get("dp"))
        if isinstance(axis, tuple):
            axis = axis[-1]
        y = fc.fft_conv_seq_sharded(v, filt, mesh, axis)
    else:
        y = fc.fft_conv(v, filt)
    y = y + v * p["skip"].astype(dt)
    y = y * jax.nn.silu(gate)
    return shard_act(y @ p["w_out"].astype(dt), "dp", None, None)


def fftconv_decode(p: Dict, cfg: ArchConfig, x: jax.Array, hist: jax.Array,
                   pos: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One-token long-conv step: y_t = sum_{j<=t} k[t-j] v_j over the cached
    value history. x (B,1,d); hist (B,S_max,d); pos (B,) current index."""
    from repro.core import fftconv as fc
    dt = x.dtype
    b, _, d = x.shape
    s_max = hist.shape[1]
    vg = x @ p["w_in"].astype(dt)
    v, gate = jnp.split(vg, 2, axis=-1)
    hist = jax.vmap(lambda h, u, i: jax.lax.dynamic_update_slice(
        h, u.astype(h.dtype), (i, 0)))(hist, v, pos)
    filt = fc.materialize_filter(p["filt"].astype(jnp.float32), s_max)  # (d,S)
    lag = pos[:, None] - jnp.arange(s_max)[None, :]             # (B, S)
    kk = jnp.take(filt, jnp.clip(lag, 0, s_max - 1), axis=1)    # (d, B, S)
    kk = jnp.where((lag >= 0)[None], kk, 0.0)
    y = jnp.einsum("bsd,dbs->bd", hist.astype(jnp.float32), kk)[:, None, :]
    y = y.astype(dt) + v * p["skip"].astype(dt)
    y = y * jax.nn.silu(gate)
    return y @ p["w_out"].astype(dt), hist

"""Functional model layers (pure JAX, explicit parameter pytrees).

Everything here is shape-polymorphic and mesh-aware but *mesh-optional*:
pass ``mesh_ctx=None`` for single-device smoke tests, or a
:class:`MeshContext` for pjit/shard_map distribution. Attention follows a
chunked flash formulation (never materializes S×S for long sequences) and
doubles as the reference oracle for the Pallas kernels in
``repro.kernels``; MoE uses sort-based capacity dispatch inside
``shard_map`` (expert × d_ff factorization of the model axis); Mamba2 uses
the chunked SSD (state-space duality) algorithm — matmul-rich intra-chunk
work for the MXU, tiny inter-chunk recurrence.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .config import ModelConfig

Params = Dict[str, Any]


# ===========================================================================
# Mesh context & sharding helpers
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class MeshContext:
    """Carries the mesh and logical-axis mapping through the model."""
    mesh: Any                       # jax.sharding.Mesh
    batch_axes: Tuple[str, ...]     # e.g. ("data",) or ("pod", "data")
    model_axis: str = "model"
    shard_seq: bool = True          # sequence-parallel residual stream
    #: route dense projections through shard_map with the sequence
    #: all-gather inside the differentiated region: forward gathers a
    #: 1/TP-sized shard instead of all-reducing a full partial sum, and
    #: the backward of the gather is a reduce-scatter (Megatron-SP).
    #: Baseline (False) relies on XLA SPMD, which emits full all-reduces
    #: for partial-sum matmuls — see EXPERIMENTS.md §Perf.
    sp_matmuls: bool = False

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def fsdp_axes(self):
        """Axes the FSDP (ZeRO-3) domain spans — the full DP domain."""
        return self.batch_axes

    @property
    def data_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))

    def constraint(self, x, spec: P):
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))


def cst(ctx: Optional[MeshContext], x, *axes):
    """Apply a sharding constraint when a mesh is present; no-op otherwise.

    ``axes`` entries: "batch" → ctx.batch_axes, "model" → model axis,
    None → unsharded.
    """
    if ctx is None:
        return x
    spec = []
    for a in axes:
        if a == "batch":
            spec.append(ctx.batch_axes)
        elif a == "model":
            spec.append(ctx.model_axis)
        else:
            spec.append(None)
    return ctx.constraint(x, P(*spec))


# ===========================================================================
# Primitives
# ===========================================================================

def rms_norm(x, scale, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def init_rms_norm(d: int, dtype) -> Params:
    return {"scale": jnp.zeros((d,), dtype)}


def dense(x, w, dtype):
    return jnp.einsum("...d,df->...f", x, w.astype(dtype))


def _rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, hd]; positions: [..., S] int32."""
    hd = x.shape[-1]
    freqs = jnp.asarray(_rope_freqs(hd, theta), jnp.float32)
    ang = positions[..., :, None].astype(jnp.float32)[..., None, :] * freqs  # [...,S,1,hd/2]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": functools.partial(jax.nn.gelu, approximate=True)}[name]


# ===========================================================================
# Attention (chunked flash, GQA via padded uniform groups)
# ===========================================================================

def init_attention(cfg: ModelConfig, key, dtype) -> Params:
    pad = cfg.gqa
    D, hd = cfg.d_model, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02

    def head_pad_init(k, n_slots, slot_to_orig):
        w = jax.random.normal(k, (D, n_slots, hd), dtype) * std
        mask = jnp.asarray([1.0 if o >= 0 else 0.0 for o in slot_to_orig], dtype)
        return w * mask[None, :, None]

    wq = head_pad_init(k1, pad.n_q_pad, pad.q_slot_to_q)
    wk = head_pad_init(k2, pad.n_kv_pad, pad.kv_slot_to_kv)
    wv = head_pad_init(k3, pad.n_kv_pad, pad.kv_slot_to_kv)
    wo = jax.random.normal(k4, (pad.n_q_pad, hd, D), dtype) * std
    womask = jnp.asarray([1.0 if o >= 0 else 0.0 for o in pad.q_slot_to_q], dtype)
    wo = wo * womask[:, None, None]
    return {"wq": wq, "wk": wk, "wv": wv, "wo": wo}


def _attn_weights_tied(params: Params, pad) -> Params:
    """Tie padded duplicate KV slots to their original-head weights so the
    padded model is numerically identical to the logical one. (Duplicated
    kv slots share initial weights; during training gradients differ per
    copy which is mathematically a reparameterization — for exactness tests
    we tie at init only.)"""
    return params


def flash_attention_jnp(q, k, v, q_pos, kv_pos, *, causal: bool, window: int,
                        attn_softcap: float, kv_valid_len=None,
                        q_chunk: int = 1024, kv_chunk: int = 1024):
    """Chunked (flash) attention — the reference oracle for the Pallas kernel.

    q: [B, Sq, Hq, hd] — Hq padded query heads (uniform groups)
    k, v: [B, Skv, Hkv, hd] — padded KV slots; group = Hq // Hkv
    q_pos: [B, Sq] absolute positions; kv_pos: [B, Skv]
    window: 0 ⇒ full attention, else sliding window (causal assumed)
    kv_valid_len: [B] — entries at kv index ≥ valid_len are masked (cache)

    Never materializes [Sq, Skv] for the full sequence: scans q chunks
    (outer) × kv chunks (inner) with running (max, sum, acc).
    """
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Skv)
    nq, nk = -(-Sq // qc), -(-Skv // kc)
    # pad seq dims to chunk multiples
    def pad_to(x, n, axis):
        padw = [(0, 0)] * x.ndim
        padw[axis] = (0, n - x.shape[axis])
        return jnp.pad(x, padw) if n != x.shape[axis] else x
    qp = pad_to(q, nq * qc, 1)
    kp = pad_to(k, nk * kc, 1)
    vp = pad_to(v, nk * kc, 1)
    qpos = pad_to(q_pos, nq * qc, 1)
    kpos = pad_to(kv_pos, nk * kc, 1)
    kv_len = kv_valid_len if kv_valid_len is not None else jnp.full((B,), Skv, jnp.int32)

    # [B, nq, qc, Hkv, G, hd]
    qg = qp.reshape(B, nq, qc, Hkv, G, hd)
    kg = kp.reshape(B, nk, kc, Hkv, hd)
    vg = vp.reshape(B, nk, kc, Hkv, hd)
    qposc = qpos.reshape(B, nq, qc)
    kposc = kpos.reshape(B, nk, kc)

    def q_block(qi):
        # transpose q to the score layout ONCE per q block — inside the kv
        # step the einsum would re-transpose it per chunk (§Perf: ~2 TB of
        # transpose traffic at qwen3/train_4k)
        qb = qg[:, qi].transpose(0, 2, 3, 1, 4)     # [B, Hkv, G, qc, hd]
        qpb = qposc[:, qi]        # [B, qc]

        def kv_step(carry, ki):
            m, l, acc = carry
            kb, vb = kg[:, ki], vg[:, ki]           # [B, kc, Hkv, hd]
            kpb = kposc[:, ki]                      # [B, kc]
            qpb_ = qpb
            s = jnp.einsum("bkgqh,bskh->bkgqs", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            if attn_softcap:
                s = attn_softcap * jnp.tanh(s / attn_softcap)
            # mask: causal, window, cache validity
            dq = qpb_[:, None, None, :, None]       # [B,1,1,qc,1]
            dk = kpb[:, None, None, None, :]        # [B,1,1,1,kc]
            ok = jnp.ones_like(s, dtype=bool)
            if causal:
                ok &= dk <= dq
            # window may be a traced per-layer scalar; 0 ⇒ full attention
            win = jnp.asarray(window, jnp.int32)
            lo = jnp.where(win > 0, dq - win, jnp.int32(-(2 ** 30)))
            ok &= dk > lo
            ok &= (jnp.arange(kc)[None, :] + ki * kc
                   < kv_len[:, None])[:, None, None, None, :]
            s = jnp.where(ok, s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows (m_new = -inf): exp(-inf - -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hkv, G, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qc), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, qc, hd), jnp.float32)
        # checkpoint the kv step: backward recomputes the [qc, kc] score /
        # prob tiles from (q, k) instead of saving them for every chunk
        # pair — the flash-attention backward. Without this the saved
        # tiles are O(S²) and defeat the chunking entirely.
        kv_step_ck = jax.checkpoint(
            kv_step, policy=jax.checkpoint_policies.nothing_saveable)
        (m, l, acc), _ = jax.lax.scan(kv_step_ck, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]       # [B,Hkv,G,qc,hd]
        return out.transpose(0, 3, 1, 2, 4)                 # [B,qc,Hkv,G,hd]

    outs = jax.lax.map(q_block, jnp.arange(nq))             # [nq,B,qc,Hkv,G,hd]
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * qc, Hq, hd)
    return out[:, :Sq].astype(q.dtype)


def decode_attention_jnp(q, k_cache, v_cache, kv_len, *, window: int,
                         attn_softcap: float, ring: bool = False):
    """Single-token attention against a KV cache.

    q: [B, Hq, hd]; k_cache/v_cache: [B, Sc, Hkv, hd]; kv_len: [B] number of
    valid cache entries (= current absolute position + 1). With ``ring``
    the cache is a ring buffer of size ``window`` (SWA): absolute position
    of slot j is recovered from kv_len.
    """
    B, Sc, Hkv, hd = k_cache.shape
    Hq = q.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    # Chunked online-softmax decode read (the jnp twin of the Pallas
    # gqa_decode kernel): the cache is streamed in kv blocks with f32
    # running (max, sum, acc). Monolithic formulations (one big matvec or
    # mul-reduce over the full 32k cache) trip XLA-CPU float
    # normalization into materializing f32 copies of the whole cache —
    # chunking keeps any legalization cast at block granularity
    # (§Perf iteration 1).
    blk = min(2048, Sc)
    nk = -(-Sc // blk)
    pad = nk * blk - Sc
    kc_ = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k_cache
    vc_ = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v_cache
    scale = 1.0 / math.sqrt(hd)
    win = jnp.asarray(window, jnp.int32)

    def kv_step(j, carry):
        m_prev, l_prev, acc = carry
        # dynamic_slice chunk reads (no transposed cache copy)
        kb = jax.lax.dynamic_slice_in_dim(kc_, j * blk, blk, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vc_, j * blk, blk, axis=1)
        sb = jnp.einsum("bkgh,bskh->bkgs", qg, kb,
                        preferred_element_type=jnp.float32) * scale
        if attn_softcap:
            sb = attn_softcap * jnp.tanh(sb / attn_softcap)
        idx = j * blk + jnp.arange(blk)[None, :]        # [1, blk]
        if ring:
            valid = ((idx < kv_len[:, None]) | (kv_len[:, None] > Sc)) \
                & (idx < Sc)
        else:
            valid = (idx < kv_len[:, None]) & (idx < Sc)
            lo = jnp.where(win > 0, kv_len[:, None] - 1 - win,
                           jnp.int32(-(2 ** 30)))
            valid &= idx > lo
        sb = jnp.where(valid[:, None, None, :], sb, -1e30)
        m_new = jnp.maximum(m_prev, sb.max(-1))
        m_safe = jnp.maximum(m_new, -1e20)
        p = jnp.exp(sb - m_safe[..., None])
        corr = jnp.exp(jnp.maximum(m_prev, -1e20) - m_safe) \
            * (m_prev > -5e29).astype(jnp.float32)
        l_new = l_prev * corr + p.sum(-1)
        pv = jnp.einsum("bkgs,bskh->bkgh", p.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        return (m_new, l_new, acc * corr[..., None] + pv)

    m0 = jnp.full((B, Hkv, G), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, kv_step, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Hq, hd).astype(q.dtype)


def attention_block(params: Params, cfg: ModelConfig, x, positions, *,
                    ctx: Optional[MeshContext], window: int,
                    kv_cache: Optional[Tuple] = None, kv_len=None,
                    ring: bool = False, d_model: Optional[int] = None):
    """Full attention sub-block: qkv proj → rope → flash/decode → out proj.

    Returns (out, new_kv) where new_kv is (k, v) to store when caching.
    x: [B, S, D]; decode when S == 1 and kv_cache is not None.
    """
    dt = jnp.dtype(cfg.dtype)
    pad = cfg.gqa
    if _sp_sharded(ctx, x):
        # train AND prefill: q/k/v computed identically; prefill writes the
        # SP-produced k/v into the cache below
        x = cst(ctx, x, "batch", "model", None)       # seq-sharded in
        q, k, v = sp_qkv(ctx, cfg, x, params["wq"], params["wk"],
                         params["wv"])
    else:
        x = cst(ctx, x, "batch", None, None)  # gather seq for attention
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
        q = cst(ctx, q, "batch", None, "model", None)
        k = cst(ctx, k, "batch", None, "model", None)
        v = cst(ctx, v, "batch", None, "model", None)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if kv_cache is None:
        o = flash_attention_jnp(
            q, k, v, positions, positions, causal=cfg.causal,
            window=window, attn_softcap=cfg.attn_softcap)
        from jax.ad_checkpoint import checkpoint_name
        o = checkpoint_name(o, "attn_out")
        new_kv = (k, v)
    else:
        ck, cv = kv_cache
        if q.shape[1] == 1:  # decode: write then attend
            B, Sc = ck.shape[0], ck.shape[1]
            # Static batching: decode positions are uniform across the
            # batch, so the cache write is ONE dynamic_update_slice at a
            # scalar step index. (A vmapped per-row DUS lowers to scatter,
            # and XLA-CPU legalizes bf16 scatter through f32 — which made
            # the layer scan carry f32 shadow copies of the whole cache:
            # ~2 TB/step at yi-34B/32k. §Perf iteration 1.) Ragged
            # positions (continuous batching) use the Pallas decode kernel
            # on TPU, which writes per-row natively.
            slot = (positions[0, 0] % Sc) if ring else positions[0, 0]
            ck = jax.lax.dynamic_update_slice(ck, k, (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v, (0, slot, 0, 0))
            # barrier: XLA commutes the f32 accumulation cast onto the
            # cache operand (convert(mul(..)) → mul(convert(..))) and then
            # promotes the whole scanned cache carry to f32; the barrier
            # pins the cast at slice granularity (§Perf iteration 1).
            ck_use, cv_use = jax.lax.optimization_barrier((ck, cv))
            o = decode_attention_jnp(
                q[:, 0], ck_use, cv_use, kv_len, window=window,
                attn_softcap=cfg.attn_softcap, ring=ring)[:, None]
        else:                 # prefill into cache
            B, S = q.shape[:2]
            Sc = ck.shape[1]
            if ring and S > Sc:
                kw, vw = k[:, -Sc:], v[:, -Sc:]
                # ring layout: slot j = pos % Sc
                roll = (positions[:, -Sc:][:, 0]) % Sc
                kw = jax.vmap(lambda a, r: jnp.roll(a, r, axis=0))(kw, roll)
                vw = jax.vmap(lambda a, r: jnp.roll(a, r, axis=0))(vw, roll)
                ck, cv = kw, vw
            else:
                ck = jax.lax.dynamic_update_slice(ck, k, (0, 0, 0, 0))
                cv = jax.lax.dynamic_update_slice(cv, v, (0, 0, 0, 0))
            o = flash_attention_jnp(
                q, k, v, positions, positions, causal=cfg.causal,
                window=window, attn_softcap=cfg.attn_softcap)
        new_kv = (ck, cv)

    if _sp_sharded(ctx, o):
        out = sp_out_proj(ctx, cfg, o, params["wo"])
    else:
        o = cst(ctx, o, "batch", None, "model", None)
        out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt))
        out = cst(ctx, out, "batch",
                  "model" if (ctx and ctx.shard_seq) else None, None)
    return out, new_kv


# ===========================================================================
# Dense MLP (SwiGLU / GeLU)
# ===========================================================================

def init_mlp(cfg: ModelConfig, key, dtype) -> Params:
    D, F = cfg.d_model, cfg.d_ff_pad
    k1, k2, k3 = jax.random.split(key, 3)
    std = 0.02
    return {
        "w_gate": jax.random.normal(k1, (D, F), dtype) * std,
        "w_up": jax.random.normal(k2, (D, F), dtype) * std,
        "w_down": jax.random.normal(k3, (F, D), dtype) * std,
    }


def mlp_block(params: Params, cfg: ModelConfig, x, *, ctx: Optional[MeshContext]):
    dt = jnp.dtype(cfg.dtype)
    if _sp_sharded(ctx, x):
        x = cst(ctx, x, "batch", "model", None)
        return sp_mlp(ctx, cfg, x, params["w_gate"], params["w_up"],
                      params["w_down"])
    x = cst(ctx, x, "batch", None, None)
    g = dense(x, params["w_gate"], dt)
    u = dense(x, params["w_up"], dt)
    g = cst(ctx, g, "batch", None, "model")
    u = cst(ctx, u, "batch", None, "model")
    h = _act(cfg.act)(g) * u
    out = dense(h, params["w_down"], dt)
    out = cst(ctx, out, "batch", "model" if (ctx and ctx.shard_seq) else None, None)
    return out


# ===========================================================================
# Sequence-parallel (Megatron-SP) projection paths — shard_map
# ===========================================================================

def _sp_sharded(ctx, x) -> bool:
    """SP path applies when tokens are shardable over (batch × seq).
    x: [B, S, D] activations or [B, S, Hp, hd] attention outputs."""
    return (ctx is not None and ctx.sp_matmuls and x.ndim in (3, 4)
            and x.shape[1] > 1
            and x.shape[0] % ctx.data_size == 0
            and x.shape[1] % ctx.model_size == 0)


def sp_qkv(ctx: MeshContext, cfg: ModelConfig, x, wq, wk, wv):
    """x: [B, S, D] seq-sharded → (q, k, v) head-sharded. The seq
    all-gather lives inside the differentiated region, so its transpose is
    a reduce-scatter (vs the baseline's full dx all-reduce)."""
    dt = jnp.dtype(cfg.dtype)
    m, fs, b = ctx.model_axis, ctx.fsdp_axes, ctx.batch_axes

    def body(xl, wql, wkl, wvl):
        xg = jax.lax.all_gather(xl, m, axis=1, tiled=True)
        wq_ = jax.lax.all_gather(wql.astype(dt), fs, axis=0, tiled=True)
        wk_ = jax.lax.all_gather(wkl.astype(dt), fs, axis=0, tiled=True)
        wv_ = jax.lax.all_gather(wvl.astype(dt), fs, axis=0, tiled=True)
        q = jnp.einsum("bsd,dhk->bshk", xg, wq_)
        k = jnp.einsum("bsd,dhk->bshk", xg, wk_)
        v = jnp.einsum("bsd,dhk->bshk", xg, wv_)
        return q, k, v

    hspec = P(b, None, m, None)
    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(b, m, None), P(fs, m, None), P(fs, m, None),
                  P(fs, m, None)),
        out_specs=(hspec, hspec, hspec), check_vma=False)(x, wq, wk, wv)


def sp_out_proj(ctx: MeshContext, cfg: ModelConfig, o, wo):
    """o: [B, S, Hp, hd] head-sharded → residual delta seq-sharded via an
    explicit psum_scatter (baseline: full all-reduce + reshard)."""
    dt = jnp.dtype(cfg.dtype)
    m, fs, b = ctx.model_axis, ctx.fsdp_axes, ctx.batch_axes

    def body(ol, wol):
        wo_ = jax.lax.all_gather(wol.astype(dt), fs, axis=2, tiled=True)
        part = jnp.einsum("bshk,hkd->bsd", ol, wo_)
        return jax.lax.psum_scatter(part, m, scatter_dimension=1, tiled=True)

    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(b, None, m, None), P(m, None, fs)),
        out_specs=P(b, m, None), check_vma=False)(o, wo)


def sp_mlp(ctx: MeshContext, cfg: ModelConfig, x, wg, wu, wd):
    """Fused SP MLP: gather seq once, TP over d_ff, psum_scatter out."""
    dt = jnp.dtype(cfg.dtype)
    m, fs, b = ctx.model_axis, ctx.fsdp_axes, ctx.batch_axes
    act = _act(cfg.act)

    def body(xl, wgl, wul, wdl):
        xg = jax.lax.all_gather(xl, m, axis=1, tiled=True)
        wg_ = jax.lax.all_gather(wgl.astype(dt), fs, axis=0, tiled=True)
        wu_ = jax.lax.all_gather(wul.astype(dt), fs, axis=0, tiled=True)
        wd_ = jax.lax.all_gather(wdl.astype(dt), fs, axis=1, tiled=True)
        h = act(jnp.einsum("bsd,df->bsf", xg, wg_)) \
            * jnp.einsum("bsd,df->bsf", xg, wu_)
        part = jnp.einsum("bsf,fd->bsd", h, wd_)
        return jax.lax.psum_scatter(part, m, scatter_dimension=1, tiled=True)

    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(b, m, None), P(fs, m), P(fs, m), P(m, fs)),
        out_specs=P(b, m, None), check_vma=False)(x, wg, wu, wd)


# ===========================================================================
# MoE (capacity-based, sort dispatch, shard_map expert×ff parallel)
# ===========================================================================

def _moe_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    """Per-expert capacity. Decode-sized batches (≤256 assignment slots)
    get lossless capacity so no token is ever dropped while generating;
    training/prefill use the standard capacity-factor rule."""
    if n_tokens * cfg.top_k <= 256:
        return n_tokens * cfg.top_k
    return max(1, int(n_tokens * cfg.top_k * cfg.capacity_factor
                      // cfg.n_experts))


def init_moe(cfg: ModelConfig, key, dtype) -> Params:
    D, F, E = cfg.d_model, cfg.d_ff_pad, cfg.n_experts
    k0, k1, k2, k3 = jax.random.split(key, 4)
    std = 0.02
    return {
        "router": jax.random.normal(k0, (D, E), jnp.float32) * std,
        "w_gate": jax.random.normal(k1, (E, D, F), dtype) * std,
        "w_up": jax.random.normal(k2, (E, D, F), dtype) * std,
        "w_down": jax.random.normal(k3, (E, F, D), dtype) * std,
    }


def _moe_local(x, gate_w, up_w, down_w, router, cfg: ModelConfig,
               e0: int, n_local: int, capacity: int):
    """Route local tokens to local experts [e0, e0+n_local) and compute.

    x: [T, D]. Returns the (partial) output [T, D] — caller psums across
    expert/ff shards. Sort-based dispatch: no one-hot dispatch einsums, so
    HLO FLOPs stay proportional to *active* expert compute.
    """
    T, Dm = x.shape
    E, k = cfg.n_experts, cfg.top_k
    dt = x.dtype
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                  # [T, k]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    flat_e = idx.reshape(-1)                              # [T*k]
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e)                           # stable
    se, st = flat_e[order], flat_t[order]
    counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * k, dtype=jnp.int32) - starts[se]
    local = (se >= e0) & (se < e0 + n_local) & (pos < capacity)
    n_slots = n_local * capacity
    slot = jnp.where(local, (se - e0) * capacity + pos, n_slots)

    # Dispatch/combine are pure GATHERS; the only scatters are 1-D int32
    # slot maps (XLA's scatter expander materializes update-shaped index
    # matrices — [T·k, D]-sized scatters cost ~16 GiB of temps at 4k·256).
    slot_token = jnp.zeros((n_slots + 1,), jnp.int32).at[slot].set(st)
    slot_valid = jnp.zeros((n_slots + 1,), jnp.bool_).at[slot].set(local)
    xb = x[slot_token[:-1]] * slot_valid[:-1, None].astype(dt)
    xb = xb.reshape(n_local, capacity, Dm)
    g = jnp.einsum("ecd,edf->ecf", xb, gate_w.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xb, up_w.astype(dt))
    h = _act(cfg.act)(g) * u
    yb = jnp.einsum("ecf,efd->ecd", h, down_w.astype(dt)).reshape(-1, Dm)
    yb = jnp.concatenate([yb, jnp.zeros((1, Dm), dt)], axis=0)

    # combine: k gathers in original assignment order, summed
    inv = jnp.argsort(order)                              # [T*k]
    yslot = slot[inv].reshape(T, k)                       # slot per (t, j)
    gweight = gate.astype(dt) * local[inv].reshape(T, k).astype(dt)
    out = jnp.zeros((T, Dm), dt)
    for j in range(k):
        out = out + yb[yslot[:, j]] * gweight[:, j:j + 1]
    return out


def moe_block(params: Params, cfg: ModelConfig, x, *, ctx: Optional[MeshContext]):
    """MoE FFN. x: [B, S, D]. Tokens sharded over batch axes. Two modes:

    * **ep** (``n_experts % model_size == 0``): experts sharded over the
      model axis (expert parallelism); each model shard builds capacity
      batches only for its experts; outputs psum over the model axis.
    * **tp** (otherwise, e.g. mixtral's 8 experts on 16 shards): every
      shard holds all experts but only a d_ff slice (tensor parallelism
      within experts); partial down-projections psum over the model axis.

    Both modes FSDP the d_model dimension over the data axis and all-gather
    it inside the shard_map body (one gather per layer, overlapped by XLA
    with the previous layer under scan).
    """
    B, S, D = x.shape
    dt = jnp.dtype(cfg.dtype)

    if ctx is None:
        capacity = _moe_capacity(B * S, cfg)
        out = _moe_local(
            x.reshape(-1, D), params["w_gate"], params["w_up"],
            params["w_down"], params["router"], cfg, 0, cfg.n_experts,
            capacity)
        return out.reshape(B, S, D)

    mesh = ctx.mesh
    msize = ctx.model_size
    ep_mode = cfg.n_experts % msize == 0
    n_local = cfg.n_experts // msize if ep_mode else cfg.n_experts
    T_local = (B * S) // ctx.data_size if B % ctx.data_size == 0 else B * S
    capacity = _moe_capacity(T_local, cfg)

    m, fs = ctx.model_axis, ctx.fsdp_axes
    # decode-sized batches (B < data shards) cannot shard tokens: run the
    # routing replicated over the data axes (trivial work per step)
    shardable = B % ctx.data_size == 0
    sp = ctx.sp_matmuls and shardable and S % msize == 0

    def body(xl, router, gw, uw, dw):
        # xl: [B/ddp, S, D] — replicated over the model axis.
        # Cast to compute dtype BEFORE the FSDP gather (halves gather bytes).
        gw = jax.lax.all_gather(gw.astype(dt), fs, axis=1, tiled=True)
        uw = jax.lax.all_gather(uw.astype(dt), fs, axis=1, tiled=True)
        dw = jax.lax.all_gather(dw.astype(dt), fs, axis=2, tiled=True)
        e0 = jax.lax.axis_index(m) * n_local if ep_mode else 0
        out = _moe_local(xl.reshape(-1, D), gw, uw, dw, router,
                         cfg, e0, n_local, capacity)
        out = out.reshape(xl.shape)
        if sp:
            # SP: combine expert partial sums straight into the seq-sharded
            # residual — 1/TP the operand bytes of a full all-reduce
            return jax.lax.psum_scatter(out, m, scatter_dimension=1,
                                        tiled=True)
        return jax.lax.psum(out, m)

    bspec = P(ctx.batch_axes, None, None) if shardable else P(None, None, None)
    ospec = P(ctx.batch_axes, m, None) if sp else bspec
    if ep_mode:
        gu_spec = P(m, fs, None)      # [E, D, F] — experts over model
        dn_spec = P(m, None, fs)      # [E, F, D]
    else:
        gu_spec = P(None, fs, m)      # [E, D, F] — d_ff over model
        dn_spec = P(None, m, fs)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(bspec, P(None, None), gu_spec, gu_spec, dn_spec),
        out_specs=ospec,
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return cst(ctx, out, "batch", "model" if ctx.shard_seq else None, None)


# ===========================================================================
# Mamba2 (SSD — state-space duality, chunked)
# ===========================================================================

def init_mamba(cfg: ModelConfig, key, dtype) -> Params:
    D = cfg.d_model
    din, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_ch = din + 2 * N
    d_in_proj = 2 * din + 2 * N + H
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02
    return {
        "in_proj": jax.random.normal(k1, (D, d_in_proj), dtype) * std,
        "conv_w": jax.random.normal(k2, (cfg.conv_width, conv_ch), dtype) * std,
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H).astype(jnp.float32)),
        "D_skip": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.asarray(
            np.log(np.expm1(np.linspace(1e-3, 0.1, H))), jnp.float32),
        "norm_scale": jnp.zeros((din,), dtype),
        "out_proj": jax.random.normal(k4, (din, D), dtype) * std,
    }


def _segsum(x):
    """x: [..., T] → lower-triangular pairwise cumulative sums [..., T, T]."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    d = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, d, -jnp.inf)


def ssd_chunked(X, dtA, B, C, chunk: int, initial_state=None):
    """Chunked SSD scan (Mamba2 Alg. from arXiv:2405.21060, jnp).

    X:   [b, l, h, p]   (already multiplied by Δ)
    dtA: [b, l, h]      (Δ·A, negative)
    B,C: [b, l, n]      (single group, broadcast over heads)
    Returns (Y [b, l, h, p], final_state [b, h, p, n]).
    """
    b, l, h, p = X.shape
    n = B.shape[-1]
    nc = l // chunk
    Xc = X.reshape(b, nc, chunk, h, p)
    Ac = dtA.reshape(b, nc, chunk, h).transpose(0, 3, 1, 2)  # [b,h,c,q]
    Bc = B.reshape(b, nc, chunk, n)
    Cc = C.reshape(b, nc, chunk, n)
    A_cum = jnp.cumsum(Ac, axis=-1)                          # [b,h,c,q]

    # 1. intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(Ac))                                 # [b,h,c,q,q]
    Y_diag = jnp.einsum("bcqn,bcsn,bhcqs,bcshp->bcqhp",
                        Cc, Bc, L, Xc)

    # 2. chunk-final states
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)          # [b,h,c,q]
    states = jnp.einsum("bcsn,bhcs,bcshp->bchpn", Bc, decay_states, Xc)

    # 3. inter-chunk recurrence (tiny scan over chunk dim)
    chunk_decay = jnp.exp(A_cum[..., -1])                    # [b,h,c]

    def step(carry, inp):
        s_prev = carry
        s_new, dec = inp
        s = s_prev * dec[..., None, None] + s_new
        return s, s_prev

    s0 = initial_state if initial_state is not None else \
        jnp.zeros((b, h, p, n), X.dtype)
    st_seq = states.transpose(1, 0, 2, 3, 4)                 # [c,b,h,p,n]
    dec_seq = chunk_decay.transpose(2, 0, 1)                 # [c,b,h]
    final, prev_states = jax.lax.scan(step, s0, (st_seq, dec_seq))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)       # [b,c,h,p,n]

    # 4. inter-chunk output
    state_decay = jnp.exp(A_cum)                             # [b,h,c,q]
    Y_off = jnp.einsum("bcqn,bchpn,bhcq->bcqhp", Cc, prev_states, state_decay)
    Y = (Y_diag + Y_off).reshape(b, l, h, p)
    return Y, final


def ssd_decode_step(x, dtA, B, C, state):
    """One-token SSD recurrence. x: [b,h,p], dtA: [b,h], B/C: [b,n]."""
    decay = jnp.exp(dtA)[..., None, None]                    # [b,h,1,1]
    state = state * decay + jnp.einsum("bn,bhp->bhpn", B, x)
    y = jnp.einsum("bn,bhpn->bhp", C, state)
    return y, state


def mamba_block(params: Params, cfg: ModelConfig, x, *,
                ctx: Optional[MeshContext],
                cache: Optional[Tuple] = None):
    """Mamba2 block. x: [B, S, D]. cache = (conv_state [B, cw-1, ch],
    ssm_state [B, H, P, N]) for decode; None for train/prefill.

    Returns (out, new_cache).
    """
    dt_ = jnp.dtype(cfg.dtype)
    Bsz, S, D = x.shape
    din, N, H, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    conv_ch = din + 2 * N
    x = cst(ctx, x, "batch", None, None)

    zxbcdt = dense(x, params["in_proj"], dt_)
    z, xBC, dt = jnp.split(zxbcdt, [din, 2 * din + 2 * N], axis=-1)
    z = cst(ctx, z, "batch", None, "model")
    xBC = cst(ctx, xBC, "batch", None, "model")
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))  # [B,S,H]
    dt = cst(ctx, dt, "batch", None, "model")

    cw = cfg.conv_width
    if cache is None:
        xpad = jnp.pad(xBC, ((0, 0), (cw - 1, 0), (0, 0)))
        new_conv = xpad[:, -(cw - 1):] if cw > 1 else None
    else:
        conv_state = cache[0]
        xpad = jnp.concatenate([conv_state.astype(dt_), xBC], axis=1)
        new_conv = xpad[:, -(cw - 1):] if cw > 1 else None
    # depthwise causal conv width cw
    conv = sum(xpad[:, i:i + S] * params["conv_w"][i].astype(dt_)[None, None]
               for i in range(cw))
    xBC = jax.nn.silu(conv + params["conv_b"].astype(dt_))

    xin, Bmat, Cmat = jnp.split(xBC, [din, din + N], axis=-1)
    xin = xin.reshape(Bsz, S, H, hp)
    xin = cst(ctx, xin, "batch", None, "model", None)
    # B/C are shared across SSM heads: replicate over the model axis so the
    # SSD einsums stay local per head shard (no per-chunk collectives).
    Bmat = cst(ctx, Bmat, "batch", None, None)
    Cmat = cst(ctx, Cmat, "batch", None, None)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))        # [H]
    dtA = dt * A                                              # [B,S,H]
    Xd = xin * dt.astype(dt_)[..., None]

    if cache is None or S > 1:
        pad = (-S) % cfg.ssm_chunk
        if pad:
            Xp = jnp.pad(Xd, ((0, 0), (0, pad), (0, 0), (0, 0)))
            Ap = jnp.pad(dtA, ((0, 0), (0, pad), (0, 0)))
            Bp = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0)))
            Cp = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0)))
        else:
            Xp, Ap, Bp, Cp = Xd, dtA, Bmat, Cmat
        init = cache[1].astype(jnp.float32) if cache is not None else None
        Y, final_state = ssd_chunked(
            Xp.astype(jnp.float32), Ap,
            Bp.astype(jnp.float32), Cp.astype(jnp.float32),
            cfg.ssm_chunk, initial_state=init)
        Y = Y[:, :S]
    else:
        y1, final_state = ssd_decode_step(
            Xd[:, 0].astype(jnp.float32), dtA[:, 0],
            Bmat[:, 0].astype(jnp.float32), Cmat[:, 0].astype(jnp.float32),
            cache[1].astype(jnp.float32))
        Y = y1[:, None]

    Y = Y.astype(dt_) + xin * params["D_skip"].astype(dt_)[None, None, :, None]
    Y = Y.reshape(Bsz, S, din)
    Y = rms_norm(Y * jax.nn.silu(z), params["norm_scale"], cfg.norm_eps)
    out = dense(Y, params["out_proj"], dt_)
    out = cst(ctx, out, "batch", "model" if (ctx and ctx.shard_seq) else None, None)
    new_cache = (new_conv.astype(dt_) if new_conv is not None else
                 jnp.zeros((Bsz, 0, conv_ch), dt_),
                 final_state.astype(jnp.float32))
    return out, new_cache

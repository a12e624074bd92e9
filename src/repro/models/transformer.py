"""Model assembly: parameter init + sharding specs, train/prefill/decode.

One code path covers the whole assigned zoo via :class:`ModelConfig`:

* ``dense`` / ``audio`` / ``vlm`` — [attention → MLP] × L (scan over a
  stacked parameter pytree; per-layer attention window array realizes
  gemma2's alternating local/global pattern with a single traced body);
* ``moe``   — [attention → MoE] × L;
* ``ssm``   — [Mamba2 SSD] × L;
* ``hybrid``— Mamba2 backbone in segments with shared attention+MLP blocks
  (Zamba2-style: ``n_shared_blocks`` alternating shared parameter sets)
  applied every ``shared_attn_every`` layers.

Layers are scanned (``jax.lax.scan`` over stacked params) so the HLO holds
one traced copy of each block — essential to keep 94-layer dry-run compiles
tractable — and optionally rematerialized (``jax.checkpoint`` with
``nothing_saveable``) so only the sequence-sharded residual stream is kept
alive between layers.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .config import ModelConfig, MAMBA, ATTN_FULL, ATTN_SWA


def _remat_policy(cfg):
    if cfg.remat_policy == "save_attn":
        return jax.checkpoint_policies.save_only_these_names("attn_out")
    return jax.checkpoint_policies.nothing_saveable
from . import layers as L
from .layers import MeshContext, cst

Params = Dict[str, Any]


# ===========================================================================
# Parameter init
# ===========================================================================

def _stack_init(fn, n: int, key):
    keys = jax.random.split(key, n)
    return jax.vmap(fn)(keys)


def init_params(cfg: ModelConfig, key) -> Params:
    pdt = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    D, Vp = cfg.d_model, cfg.vocab_pad
    params: Params = {}
    params["embed"] = {
        "tok": jax.random.normal(keys[0], (Vp, D), pdt) * 0.02,
    }
    if cfg.frontend == "audio":
        params["embed"]["frame_in"] = jax.random.normal(keys[5], (D, D), pdt) * 0.02
    if cfg.frontend == "vision":
        params["embed"]["patch_in"] = jax.random.normal(keys[5], (D, D), pdt) * 0.02

    kinds = cfg.layer_kinds
    n_attn = sum(1 for k in kinds if k != MAMBA)
    n_mamba = sum(1 for k in kinds if k == MAMBA)

    if cfg.family == "hybrid":
        assert n_mamba == cfg.n_layers, "hybrid backbone is all-mamba here"
        params["mamba"] = {
            "block": _stack_init(lambda k: L.init_mamba(cfg, k, pdt), n_mamba, keys[1]),
            "ln": _stack_init(lambda k: L.init_rms_norm(D, pdt), n_mamba, keys[6]),
        }
        def shared_init(k):
            k1, k2, k3, k4 = jax.random.split(k, 4)
            return {
                "ln1": L.init_rms_norm(D, pdt),
                "attn": L.init_attention(cfg, k1, pdt),
                "ln2": L.init_rms_norm(D, pdt),
                "mlp": L.init_mlp(cfg, k2, pdt),
            }
        params["shared"] = _stack_init(shared_init, cfg.n_shared_blocks, keys[2])
    elif cfg.family == "ssm":
        params["mamba"] = {
            "block": _stack_init(lambda k: L.init_mamba(cfg, k, pdt), n_mamba, keys[1]),
            "ln": _stack_init(lambda k: L.init_rms_norm(D, pdt), n_mamba, keys[6]),
        }
    else:
        def layer_init(k):
            k1, k2 = jax.random.split(k)
            lp = {
                "ln1": L.init_rms_norm(D, pdt),
                "attn": L.init_attention(cfg, k1, pdt),
                "ln2": L.init_rms_norm(D, pdt),
            }
            if cfg.n_experts:
                lp["moe"] = L.init_moe(cfg, k2, pdt)
            else:
                lp["mlp"] = L.init_mlp(cfg, k2, pdt)
            if cfg.post_norms:
                lp["ln_pa"] = L.init_rms_norm(D, pdt)
                lp["ln_pf"] = L.init_rms_norm(D, pdt)
            return lp
        params["layers"] = _stack_init(layer_init, cfg.n_layers, keys[1])

    params["final_norm"] = L.init_rms_norm(D, pdt)
    if cfg.encoder_only:
        params["head"] = jax.random.normal(keys[3], (D, Vp), pdt) * 0.02
    elif not cfg.tie_embeddings:
        params["head"] = jax.random.normal(keys[3], (D, Vp), pdt) * 0.02
    return params


# ===========================================================================
# Parameter sharding specs (FSDP over 'data', TP over 'model')
# ===========================================================================

def param_pspecs(cfg: ModelConfig, stacked: bool = True) -> Params:
    """PartitionSpec tree mirroring :func:`init_params`.

    Stacked per-layer leaves get a leading ``None`` (layer dim unsharded).
    """
    def st(*spec):
        return P(*((None,) + spec)) if stacked else P(*spec)

    attn = {"wq": st("data", "model", None), "wk": st("data", "model", None),
            "wv": st("data", "model", None), "wo": st("model", None, "data")}
    mlp = {"w_gate": st("data", "model"), "w_up": st("data", "model"),
           "w_down": st("model", "data")}
    norm = {"scale": st(None)}
    specs: Params = {"embed": {"tok": P("model", "data")}}
    if cfg.frontend == "audio":
        specs["embed"]["frame_in"] = P("data", "model")
    if cfg.frontend == "vision":
        specs["embed"]["patch_in"] = P("data", "model")

    mamba = {
        "in_proj": st("data", "model"), "conv_w": st(None, "model"),
        "conv_b": st("model"), "A_log": st(None), "D_skip": st(None),
        "dt_bias": st(None), "norm_scale": st("model"),
        "out_proj": st("model", "data"),
    }
    if cfg.family in ("hybrid", "ssm"):
        specs["mamba"] = {"block": mamba, "ln": norm}
        if cfg.family == "hybrid":
            specs["shared"] = {"ln1": norm, "attn": {k: st(*v[1:]) if False else v
                                                     for k, v in attn.items()},
                               "ln2": norm, "mlp": mlp}
            # shared blocks are stacked over n_shared_blocks too
            specs["shared"] = {
                "ln1": {"scale": P(None, None)},
                "attn": {"wq": P(None, "data", "model", None),
                         "wk": P(None, "data", "model", None),
                         "wv": P(None, "data", "model", None),
                         "wo": P(None, "model", None, "data")},
                "ln2": {"scale": P(None, None)},
                "mlp": {"w_gate": P(None, "data", "model"),
                        "w_up": P(None, "data", "model"),
                        "w_down": P(None, "model", "data")},
            }
    else:
        lp = {"ln1": norm, "attn": attn, "ln2": norm}
        if cfg.n_experts:
            if cfg.n_experts % max(cfg.tp_shards, 1) == 0:
                lp["moe"] = {"router": st(None, None),
                             "w_gate": st("model", "data", None),
                             "w_up": st("model", "data", None),
                             "w_down": st("model", None, "data")}
            else:
                lp["moe"] = {"router": st(None, None),
                             "w_gate": st(None, "data", "model"),
                             "w_up": st(None, "data", "model"),
                             "w_down": st(None, "model", "data")}
        else:
            lp["mlp"] = mlp
        if cfg.post_norms:
            lp["ln_pa"] = norm
            lp["ln_pf"] = norm
        specs["layers"] = lp

    specs["final_norm"] = {"scale": P(None)}
    if "head" in _head_keys(cfg):
        specs["head"] = P("data", "model")
    return specs


def _head_keys(cfg: ModelConfig):
    return {"head"} if (cfg.encoder_only or not cfg.tie_embeddings) else set()


def retarget_fsdp(spec_tree, fsdp_axes):
    """Replace the 'data' (FSDP) axis in a pspec tree with e.g.
    ('pod', 'data') so optimizer state shards across pods too (ZeRO over
    the full DP domain instead of within-pod only)."""
    if fsdp_axes == "data":
        return spec_tree

    def fix(spec):
        return P(*[fsdp_axes if a == "data" else a for a in spec])

    return jax.tree_util.tree_map(
        fix, spec_tree, is_leaf=lambda x: isinstance(x, P))


# ===========================================================================
# Embedding / head
# ===========================================================================

def embed_tokens(params: Params, cfg: ModelConfig, tokens, ctx):
    """Token embedding. With a mesh, the vocab-sharded table is looked up
    inside shard_map (local masked gather + psum over the model axis) —
    avoids XLA's one-hot lowering of sharded gathers, which materializes a
    [B, S, V/shards] temp (tens of GB for 256k vocabs)."""
    dt = jnp.dtype(cfg.dtype)
    emb = params["embed"]["tok"]
    small = tokens.shape[0] * tokens.shape[1] <= 4096  # decode-sized: plain take
    if ctx is None or small or tokens.shape[0] % ctx.data_size != 0:
        x = jnp.take(emb.astype(dt), tokens, axis=0)
    else:
        m, fs = ctx.model_axis, ctx.fsdp_axes
        Vp = cfg.vocab_pad
        v_local = Vp // ctx.model_size

        def body(tok, table):
            table = jax.lax.all_gather(table.astype(dt), fs, axis=1,
                                       tiled=True)  # [V/m, D]
            lo = jax.lax.axis_index(m) * v_local
            local = tok - lo
            ok = (local >= 0) & (local < v_local)
            safe = jnp.clip(local, 0, v_local - 1)
            out = jnp.take(table, safe, axis=0) * ok[..., None].astype(dt)
            return jax.lax.psum(out, m)

        x = jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(P(ctx.batch_axes, None), P(m, fs)),
            out_specs=P(ctx.batch_axes, None, None),
            check_vma=False,
        )(tokens, emb)
    if cfg.scale_embed:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dt)
    return x


def embed_input(params: Params, cfg: ModelConfig, batch: Dict[str, Any], ctx):
    dt = jnp.dtype(cfg.dtype)
    if cfg.frontend == "audio":
        x = L.dense(batch["frames"].astype(dt), params["embed"]["frame_in"], dt)
    elif cfg.frontend == "vision":
        px = L.dense(batch["patches"].astype(dt), params["embed"]["patch_in"], dt)
        tx = embed_tokens(params, cfg, batch["tokens"], ctx)
        x = jnp.concatenate([px, tx], axis=1)
    else:
        x = embed_tokens(params, cfg, batch["tokens"], ctx)
    return cst(ctx, x, "batch", "model" if (ctx and ctx.shard_seq) else None, None)


def logits_fn(params: Params, cfg: ModelConfig, x, ctx):
    dt = jnp.dtype(cfg.dtype)
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    w = params.get("head", None)
    if w is None:
        w = params["embed"]["tok"].T
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(dt)).astype(jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    # mask padded vocab slots
    if cfg.vocab_pad != cfg.vocab_size:
        neg = jnp.full((cfg.vocab_pad - cfg.vocab_size,), -1e30, jnp.float32)
        logits = logits.at[..., cfg.vocab_size:].add(neg)
    return logits


# ===========================================================================
# Layer stacks
# ===========================================================================

def _window_array(cfg: ModelConfig) -> np.ndarray:
    """Per-layer attention window (0 = full) for attention layers in order."""
    wins = [cfg.window if k == ATTN_SWA else 0
            for k in cfg.layer_kinds if k != MAMBA]
    return np.asarray(wins, np.int32)


def _attn_layer_body(cfg, ctx, positions, kv_len, ring):
    def body(x, lp, window, kv):
        h = L.rms_norm(x, lp["ln1"]["scale"], cfg.norm_eps)
        a, new_kv = L.attention_block(
            lp["attn"], cfg, h, positions, ctx=ctx, window=window,
            kv_cache=kv, kv_len=kv_len, ring=ring)
        if cfg.post_norms:
            a = L.rms_norm(a, lp["ln_pa"]["scale"], cfg.norm_eps)
        x = x + a
        h = L.rms_norm(x, lp["ln2"]["scale"], cfg.norm_eps)
        if cfg.n_experts:
            f = L.moe_block(lp["moe"], cfg, h, ctx=ctx)
        else:
            f = L.mlp_block(lp["mlp"], cfg, h, ctx=ctx)
        if cfg.post_norms:
            f = L.rms_norm(f, lp["ln_pf"]["scale"], cfg.norm_eps)
        return x + f, new_kv
    return body


def _mamba_layer_body(cfg, ctx):
    def body(x, lp, cache):
        h = L.rms_norm(x, lp["ln"]["scale"], cfg.norm_eps)
        m, new_cache = L.mamba_block(lp["block"], cfg, h, ctx=ctx, cache=cache)
        return x + m, new_cache
    return body


def run_attention_stack(params: Params, cfg: ModelConfig, x, positions, ctx,
                        cache=None, kv_len=None, ring=False):
    """Scan over stacked [attention → FFN] layers. cache: (K, V) stacked
    [L, B, Sc, KVp, hd] or None. Returns (x, new_cache)."""
    windows = jnp.asarray(_window_array(cfg))
    body = _attn_layer_body(cfg, ctx, positions, kv_len, ring)
    fn = jax.checkpoint(body, policy=_remat_policy(cfg)) \
        if cfg.remat else body

    if cache is None:
        def scan_nocache(carry, scanned):
            lp, window = scanned
            x_new, _ = fn(carry, lp, window, None)
            return x_new, None
        x, _ = jax.lax.scan(scan_nocache, x, (params["layers"], windows))
        return x, None

    def scan_withcache(carry, scanned):
        lp, window, ck, cv = scanned
        x_new, new_kv = fn(carry, lp, window, (ck, cv))
        return x_new, new_kv

    x, (nk, nv) = jax.lax.scan(
        scan_withcache, x, (params["layers"], windows, cache[0], cache[1]))
    return x, (nk, nv)


def run_mamba_stack(params: Params, cfg: ModelConfig, x, ctx, cache=None):
    """Scan over Mamba2 layers. cache: (conv [L,B,cw-1,ch], ssm [L,B,H,P,N])."""
    body = _mamba_layer_body(cfg, ctx)

    def scan_body(carry, scanned):
        lp, cc = scanned
        fn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable) \
            if cfg.remat else body
        x_new, new_cache = fn(carry, lp, cc)
        return x_new, new_cache

    mp = {"block": params["block"], "ln": params["ln"]}
    stacked = jax.tree_util.tree_map(lambda a: a, mp)
    if cache is None:
        def nocache(carry, lp):
            fn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable) \
                if cfg.remat else body
            x_new, _ = fn(carry, lp, None)
            return x_new, None
        x, _ = jax.lax.scan(
            nocache, x, {"block": params["block"], "ln": params["ln"]})
        return x, None
    conv, ssm = cache
    def withcache(carry, scanned):
        lp = {"block": scanned[0], "ln": scanned[1]}
        return scan_body(carry, (lp, (scanned[2], scanned[3])))
    x, (nc, ns) = jax.lax.scan(
        withcache, x, (params["block"], params["ln"], conv, ssm))
    return x, (nc, ns)


def run_hybrid_stack(params: Params, cfg: ModelConfig, x, positions, ctx,
                     cache=None, kv_len=None):
    """Zamba2-style: segments of Mamba layers + shared attention blocks.

    The shared block after segment ``i`` uses shared parameter set
    ``i % n_shared_blocks`` (tree-selected inside the scan body).
    """
    k = cfg.shared_attn_every
    n_seg = cfg.n_layers // k
    shared = params["shared"]
    body_m = _mamba_layer_body(cfg, ctx)

    def seg_reshape(a):
        return a.reshape((n_seg, k) + a.shape[1:])

    mamba_seg = jax.tree_util.tree_map(seg_reshape, params["mamba"])

    def select_shared(i):
        idx = i % cfg.n_shared_blocks
        return jax.tree_util.tree_map(lambda a: a[idx], shared)

    def shared_body(x, sp, kv):
        h = L.rms_norm(x, sp["ln1"]["scale"], cfg.norm_eps)
        a, new_kv = L.attention_block(
            sp["attn"], cfg, h, positions, ctx=ctx, window=0,
            kv_cache=kv, kv_len=kv_len)
        x = x + a
        h = L.rms_norm(x, sp["ln2"]["scale"], cfg.norm_eps)
        x = x + L.mlp_block(sp["mlp"], cfg, h, ctx=ctx)
        return x, new_kv

    def seg_scan(carry, scanned):
        x = carry
        seg_idx = scanned["idx"]
        # inner: k mamba layers
        def inner(c, s):
            lp = {"block": s[0], "ln": s[1]}
            fn = jax.checkpoint(body_m, policy=jax.checkpoint_policies.nothing_saveable) \
                if cfg.remat else body_m
            if "conv" in scanned:
                xn, nc = fn(c, lp, (s[2], s[3]))
                return xn, nc
            xn, _ = fn(c, lp, None)
            return xn, None
        if "conv" in scanned:
            xs = (scanned["mamba"]["block"], scanned["mamba"]["ln"],
                  scanned["conv"], scanned["ssm"])
        else:
            xs = (scanned["mamba"]["block"], scanned["mamba"]["ln"])
        x, mcache = jax.lax.scan(inner, x, xs)
        sp = select_shared(seg_idx)
        kv = (scanned["sk"], scanned["sv"]) if "sk" in scanned else None
        fn_s = jax.checkpoint(shared_body, policy=jax.checkpoint_policies.nothing_saveable) \
            if cfg.remat else shared_body
        x, new_kv = fn_s(x, sp, kv)
        out = {}
        if mcache is not None and "conv" in scanned:
            out["conv"], out["ssm"] = mcache
        if new_kv is not None and "sk" in scanned:
            out["sk"], out["sv"] = new_kv
        return x, out

    xs = {"idx": jnp.arange(n_seg), "mamba": mamba_seg}
    if cache is not None:
        conv, ssm, sk, sv = cache
        xs["conv"] = conv.reshape((n_seg, k) + conv.shape[1:])
        xs["ssm"] = ssm.reshape((n_seg, k) + ssm.shape[1:])
        xs["sk"], xs["sv"] = sk, sv
    x, outs = jax.lax.scan(seg_scan, x, xs)
    if cache is None:
        return x, None
    nconv = outs["conv"].reshape((-1,) + outs["conv"].shape[2:])
    nssm = outs["ssm"].reshape((-1,) + outs["ssm"].shape[2:])
    return x, (nconv, nssm, outs["sk"], outs["sv"])


# ===========================================================================
# Forward passes
# ===========================================================================

def forward(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
            ctx: Optional[MeshContext] = None):
    """Full-sequence forward (training / encoding). Returns final hidden."""
    x = embed_input(params, cfg, batch, ctx)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if cfg.family in ("ssm",):
        x, _ = run_mamba_stack(params["mamba"], cfg, x, ctx)
    elif cfg.family == "hybrid":
        x, _ = run_hybrid_stack(params, cfg, x, positions, ctx)
    else:
        x, _ = run_attention_stack(params, cfg, x, positions, ctx)
    return x


def softmax_xent(params, cfg, x, targets, mask, ctx, chunk: int = 512):
    """Cross-entropy over (possibly huge, padded) vocab, chunked over seq so
    [B, chunk, V] logits never exceed a bounded working set."""
    B, S, D = x.shape
    # chunk whenever the full [B, S, V] logits tensor is big (≥16k vocab):
    # §Perf iteration 6 — full-logit CE at smollm/49k vocab costs ~0.8 GiB
    # f32 per device in fwd and again in the rematerialized bwd.
    if cfg.vocab_pad <= 16384 or S <= chunk:
        logits = logits_fn(params, cfg, x, ctx)
        return _xent_from_logits(logits, targets, mask)

    nch = -(-S // chunk)
    pad = nch * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    xc = x.reshape(B, nch, chunk, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, nch, chunk).transpose(1, 0, 2)
    mc = mask.reshape(B, nch, chunk).transpose(1, 0, 2)

    def one(chunk_in):
        xb, tb, mb = chunk_in
        logits = logits_fn(params, cfg, xb, ctx)
        l, m = _xent_from_logits(logits, tb, mb, reduce=False)
        return l, m

    fn = jax.checkpoint(one, policy=jax.checkpoint_policies.nothing_saveable)
    losses, masses = jax.lax.map(fn, (xc, tc, mc))
    return losses.sum() / jnp.maximum(masses.sum(), 1.0)


def _xent_from_logits(logits, targets, mask, reduce: bool = True):
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - tgt) * mask
    if reduce:
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.sum(), mask.sum()


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
            ctx: Optional[MeshContext] = None):
    x = forward(params, cfg, batch, ctx)
    mask = batch.get("mask")
    targets = batch["targets"]
    if mask is None:
        mask = jnp.ones(targets.shape, jnp.float32)
    return softmax_xent(params, cfg, x, targets, mask.astype(jnp.float32), ctx)


# ===========================================================================
# KV/state cache
# ===========================================================================

class Cache(NamedTuple):
    """Decode-time state. Unused fields hold zero-size arrays (pytree-stable)."""
    kv_k: Any       # [L_attn, B, Sc, KVp, hd]
    kv_v: Any
    conv: Any       # [L_mamba, B, cw-1, conv_ch]
    ssm: Any        # [L_mamba, B, H, P, N]  (float32)
    pos: Any        # [B] int32 — next position to write


def cache_spec(cfg: ModelConfig, batch: int, max_seq: int
               ) -> Tuple[Dict[str, tuple], bool]:
    """Shapes/dtypes for the cache; returns (spec, ring)."""
    dt = cfg.dtype
    kinds = cfg.layer_kinds
    n_attn = sum(1 for k in kinds if k != MAMBA)
    n_mamba = sum(1 for k in kinds if k == MAMBA)
    if cfg.family == "hybrid":
        n_attn = cfg.n_layers // cfg.shared_attn_every
        n_mamba = cfg.n_layers
    ring = n_attn > 0 and all(k == ATTN_SWA for k in kinds if k != MAMBA) \
        and cfg.window < max_seq and cfg.family != "hybrid"
    Sc = cfg.window if ring else max_seq
    pad = cfg.gqa
    spec = {
        "kv_k": ((n_attn, batch, Sc, pad.n_kv_pad, cfg.head_dim), dt),
        "kv_v": ((n_attn, batch, Sc, pad.n_kv_pad, cfg.head_dim), dt),
        "conv": ((n_mamba, batch, max(cfg.conv_width - 1, 0),
                  cfg.d_inner + 2 * cfg.ssm_state if n_mamba else 0), dt),
        "ssm": ((n_mamba, batch, cfg.ssm_heads if n_mamba else 0,
                 cfg.ssm_head_dim, cfg.ssm_state), "float32"),
        "pos": ((batch,), "int32"),
    }
    return spec, ring


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> Tuple[Cache, bool]:
    spec, ring = cache_spec(cfg, batch, max_seq)
    return Cache(**{k: jnp.zeros(s, jnp.dtype(d))
                    for k, (s, d) in spec.items()}), ring


def cache_pspecs(cfg: ModelConfig) -> Cache:
    """Sharding: batch over data axes; padded KV heads over model."""
    return Cache(
        kv_k=P(None, "data", None, "model", None),
        kv_v=P(None, "data", None, "model", None),
        conv=P(None, "data", None, "model"),
        ssm=P(None, "data", "model", None, None),
        pos=P("data"),
    )


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
            cache: Cache, ring: bool, ctx: Optional[MeshContext] = None
            ) -> Tuple[Any, Cache]:
    """Run the prompt through the model, filling the cache.
    Returns (last-position logits [B, Vp], cache)."""
    x = embed_input(params, cfg, batch, ctx)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    kv_len = jnp.full((B,), S, jnp.int32)
    if cfg.family == "ssm":
        x, mc = run_mamba_stack(params["mamba"], cfg, x, ctx,
                                cache=(cache.conv, cache.ssm))
        new = cache._replace(conv=mc[0], ssm=mc[1], pos=cache.pos + S)
    elif cfg.family == "hybrid":
        x, hc = run_hybrid_stack(params, cfg, x, positions, ctx,
                                 cache=(cache.conv, cache.ssm,
                                        cache.kv_k, cache.kv_v),
                                 kv_len=kv_len)
        new = cache._replace(conv=hc[0], ssm=hc[1], kv_k=hc[2], kv_v=hc[3],
                             pos=cache.pos + S)
    else:
        x, kv = run_attention_stack(params, cfg, x, positions, ctx,
                                    cache=(cache.kv_k, cache.kv_v),
                                    kv_len=kv_len, ring=ring)
        new = cache._replace(kv_k=kv[0], kv_v=kv[1], pos=cache.pos + S)
    logits = logits_fn(params, cfg, x[:, -1:], ctx)[:, 0]
    return logits, new


def decode_step(params: Params, cfg: ModelConfig, token, cache: Cache,
                ring: bool, ctx: Optional[MeshContext] = None
                ) -> Tuple[Any, Cache]:
    """One decode step. token: [B] int32. Returns (logits [B, Vp], cache)."""
    x = embed_tokens(params, cfg, token[:, None], ctx)
    B = x.shape[0]
    positions = cache.pos[:, None]
    kv_len = cache.pos + 1
    if cfg.family == "ssm":
        x, mc = run_mamba_stack(params["mamba"], cfg, x, ctx,
                                cache=(cache.conv, cache.ssm))
        new = cache._replace(conv=mc[0], ssm=mc[1], pos=cache.pos + 1)
    elif cfg.family == "hybrid":
        x, hc = run_hybrid_stack(params, cfg, x, positions, ctx,
                                 cache=(cache.conv, cache.ssm,
                                        cache.kv_k, cache.kv_v),
                                 kv_len=kv_len)
        new = cache._replace(conv=hc[0], ssm=hc[1], kv_k=hc[2], kv_v=hc[3],
                             pos=cache.pos + 1)
    else:
        x, kv = run_attention_stack(params, cfg, x, positions, ctx,
                                    cache=(cache.kv_k, cache.kv_v),
                                    kv_len=kv_len, ring=ring)
        new = cache._replace(kv_k=kv[0], kv_v=kv[1], pos=cache.pos + 1)
    logits = logits_fn(params, cfg, x, ctx)[:, 0]
    return logits, new

"""Single-token decode steps over the SpeedMalloc paged KV cache.

The serving hot loop: embed the last sampled token, scan the layer stack —
each attention layer gathers its page-mapped KV (the *only* data-path read of
allocator-managed storage; metadata never enters the compute path, per the
paper's segregated layout) — collect the new token's K/V per layer, then hand
the whole batch of page requests to the support-core in ONE HMQ step
(`decode_append`).

Families:
  dense/moe/vlm — paged attention every layer
  hybrid        — Mamba2 recurrence + shared-attn block at every k-th layer
                  (paged KV per shared-attn *invocation*)
  ssm (rwkv6)   — pure recurrence; no paged KV (technique inapplicable,
                  DESIGN.md §4) but lane state still allocator-managed
  audio         — decoder self-attn paged + cross-attn over encoder output
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core.paged_kv import PagedKVConfig, PagedKVState, gather_kv
from . import mamba2 as m2
from . import rwkv6 as rw
from .attention import mea_attention
from .layers import apply_norm, embed, out_project, unembed, apply_rope
from .moe import MoESpec, moe_apply
from .transformer import FULL_WINDOW, layer_windows
from .layers import mlp_apply


def paged_decode_attention(
    q: jnp.ndarray,          # [B, H, hd] — new token queries
    k_gath: jnp.ndarray,     # [B, S, KV, hd] — gathered pages
    v_gath: jnp.ndarray,
    k_new: jnp.ndarray,      # [B, KV, hd] — this token's K (not yet in cache)
    v_new: jnp.ndarray,
    seq_lens: jnp.ndarray,   # [B] tokens already in cache
    active: jnp.ndarray,     # [B] bool
    window,                  # int or traced scalar (FULL_WINDOW = none)
    pos=None,                # [B, S] absolute positions (default: arange)
    gathered_valid=None,     # [B, S] extra validity (windowed gather)
) -> jnp.ndarray:
    B, S = k_gath.shape[:2]
    k = jnp.concatenate([k_gath, k_new[:, None]], axis=1)
    v = jnp.concatenate([v_gath, v_new[:, None]], axis=1)
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    pos = jnp.concatenate([pos, seq_lens[:, None]], axis=1)   # [B, S+1]
    # cache slots are valid strictly below seq_len (slot `seq_len` exists in
    # the gathered pages but is unwritten); the appended self column (last)
    # carries pos == seq_len and is always valid for active lanes.
    is_self = jnp.arange(S + 1) == S
    valid = jnp.where(is_self[None, :], True, pos < seq_lens[:, None])
    if gathered_valid is not None:
        valid = valid & jnp.concatenate(
            [gathered_valid, jnp.ones((B, 1), bool)], axis=1)
    valid = valid & (pos > seq_lens[:, None] - window)     # sliding window
    valid = valid & active[:, None]
    out = mea_attention(q[:, None], k, v, causal=False, window=None,
                        kv_valid=valid, chunk=2048)
    return out[:, 0]


def _attn_layer_step(cfg: ArchConfig, lp: dict, x, kvcfg, paged: PagedKVState,
                     kv_layer, window, positions):
    """One attention block for one new token. Returns (x, k_new, v_new).

    Named scopes ``attention`` (with ``gather_kv`` inside) and ``mlp`` mark
    its device ops in the compiled step's metadata and in profiler traces."""
    hd = cfg.resolved_head_dim
    B = x.shape[0]
    with jax.named_scope("attention"):
        h = apply_norm(cfg.norm, lp["ln_attn"], x)
        q = (h @ lp["attn"]["wq"] + lp["attn"].get("bq", 0.0)).reshape(B, cfg.num_heads, hd)
        k = (h @ lp["attn"]["wk"] + lp["attn"].get("bk", 0.0)).reshape(B, cfg.num_kv_heads, hd)
        v = (h @ lp["attn"]["wv"] + lp["attn"].get("bv", 0.0)).reshape(B, cfg.num_kv_heads, hd)
        if cfg.family != "audio":
            q = apply_rope(q[:, None], positions[:, None], cfg.rope_theta)[:, 0]
            k = apply_rope(k[:, None], positions[:, None], cfg.rope_theta)[:, 0]
        from ..distributed.hints import current_hints
        from ..perf_flags import current_flags
        hints = current_hints()
        flags = current_flags()
        static_window = getattr(cfg, "window", None)
        use_windowed = (flags.windowed_gather and static_window
                        and cfg.attn_pattern == "swa")
        with jax.named_scope("gather_kv"):
            if use_windowed:
                # SWA: gather only the slots that can be live under the
                # window — the support-core already recycled everything
                # older (DESIGN.md §2)
                from ..core.paged_kv import gather_kv_window
                k_gath, v_gath, pos, gvalid = gather_kv_window(
                    kvcfg, paged, kv_layer, static_window)
            else:
                k_gath, v_gath, _ = gather_kv(kvcfg, paged, kv_layer)
                pos = gvalid = None
            k_gath = hints.gathered_kv(k_gath, cfg.num_kv_heads)
            v_gath = hints.gathered_kv(v_gath, cfg.num_kv_heads)
        attn = paged_decode_attention(q, k_gath, v_gath, k, v,
                                      paged.seq_lens, paged.active, window,
                                      pos=pos, gathered_valid=gvalid)
        x = x + out_project(lp["attn"], attn[:, None])[:, 0]
    with jax.named_scope("mlp"):
        h = apply_norm(cfg.norm, lp["ln_mlp"], x)
        if "moe" in lp:
            spec = MoESpec(cfg.d_model, cfg.d_ff, cfg.num_experts,
                           cfg.experts_per_token,
                           capacity_factor=cfg.moe_capacity_factor, act=cfg.act)
            x = x + moe_apply(lp["moe"], spec, h[:, None])[:, 0]
        else:
            x = x + mlp_apply(lp["mlp"], h, cfg.act)
    return x, k, v


# --------------------------------------------------------------------------
# Family-specific stacks (token in -> hidden out + stacked new KV / states)
# --------------------------------------------------------------------------

class RecurrentState(NamedTuple):
    """Stacked per-layer recurrent state for ssm/hybrid families."""
    ssm: Any = None        # hybrid: [L, B, h, n, hd] | rwkv: [L, B, H, hd, hd]
    conv: Any = None       # hybrid: [L, B, K-1, conv_dim]
    tm_prev: Any = None    # rwkv: [L, B, 1, d]
    cm_prev: Any = None    # rwkv: [L, B, 1, d]


def init_recurrent_state(cfg: ArchConfig, batch: int, dtype) -> Optional[RecurrentState]:
    if cfg.family == "hybrid":
        spec = m2.make_spec(cfg.d_model, cfg.ssm_state, cfg.ssm_head_dim)
        L = cfg.num_layers
        return RecurrentState(
            ssm=jnp.zeros((L, batch, spec.heads, spec.n_state, spec.head_dim), jnp.float32),
            conv=jnp.zeros((L, batch, m2.CONV_K - 1, spec.conv_dim), dtype),
        )
    if cfg.family == "ssm":
        spec = rw.RWKV6Spec(cfg.d_model, cfg.d_ff, cfg.resolved_head_dim)
        L = cfg.num_layers
        return RecurrentState(
            ssm=jnp.zeros((L, batch, spec.heads, spec.head_dim, spec.head_dim), jnp.float32),
            tm_prev=jnp.zeros((L, batch, 1, cfg.d_model), dtype),
            cm_prev=jnp.zeros((L, batch, 1, cfg.d_model), dtype),
        )
    return None


def decode_hidden(
    params: dict,
    cfg: ArchConfig,
    kvcfg: PagedKVConfig,
    paged: PagedKVState,
    rec: Optional[RecurrentState],
    tokens: jnp.ndarray,               # [B] int32
    enc_out: Optional[jnp.ndarray] = None,   # [B, F, d] whisper
    hints=None,
    unroll: bool = False,
):
    """Run the layer stack for one token.

    Returns (hidden [B, d], new_kv ([B, L_kv, KV, hd], [B, L_kv, KV, hd]) or
    None, new_rec).
    """
    x = embed(params["embed"], tokens)
    if hints is not None:
        x = hints.lanes(x)
    positions = paged.seq_lens
    L_unroll = cfg.num_layers if unroll else 1

    if cfg.family == "ssm":
        spec = rw.RWKV6Spec(cfg.d_model, cfg.d_ff, cfg.resolved_head_dim)

        def body(h, xs):
            lp, wkv, tmp, cmp = xs
            y, new_wkv, new_tmp = rw.rwkv6_time_mix_step(
                lp["tm"], spec, apply_norm("layernorm", lp["ln1"], h),
                rw.RWKV6DecodeState(wkv=wkv, tm_prev=tmp, cm_prev=cmp))
            h = h + y
            hn = apply_norm("layernorm", lp["ln2"], h)
            y2, new_cmp = rw.rwkv6_channel_mix_step(lp["cm"], hn, cmp)
            return h + y2, (new_wkv, new_tmp, new_cmp)

        h, (wkv, tmp, cmp) = jax.lax.scan(
            body, x, (params["layers"], rec.ssm, rec.tm_prev, rec.cm_prev),
            unroll=L_unroll)
        return h, None, RecurrentState(ssm=wkv, tm_prev=tmp, cm_prev=cmp)

    if cfg.family == "hybrid":
        spec = m2.make_spec(cfg.d_model, cfg.ssm_state, cfg.ssm_head_dim)
        every = max(cfg.attn_every, 1)
        L = cfg.num_layers
        flags = (jnp.arange(L, dtype=jnp.int32) % every) == (every - 1)
        attn_slot = jnp.cumsum(flags.astype(jnp.int32)) - flags.astype(jnp.int32)
        shared = params["shared_attn"]
        windows = jnp.full((L,), FULL_WINDOW, jnp.int32)

        def body(h, xs):
            lp, ssm, conv, flag, slot, w = xs
            y, new_state = m2.mamba2_decode_step(
                lp["mamba"], spec, apply_norm(cfg.norm, lp["ln"], h),
                m2.Mamba2DecodeState(conv=conv, ssm=ssm))
            h = h + y

            def with_attn(hh):
                return _attn_layer_step(cfg, shared, hh, kvcfg, paged,
                                        slot, w, positions)

            def no_attn(hh):
                z = jnp.zeros((hh.shape[0], cfg.num_kv_heads,
                               cfg.resolved_head_dim), hh.dtype)
                return hh, z, z

            h, k, v = jax.lax.cond(flag, with_attn, no_attn, h)
            return h, (new_state.ssm, new_state.conv, k, v)

        h, (ssm, conv, ks, vs) = jax.lax.scan(
            body, x, (params["layers"], rec.ssm, rec.conv, flags, attn_slot,
                      windows), unroll=L_unroll)
        # Select only the attn-invocation rows (static index) -> [B, L_kv, KV, hd]
        idx = np.arange(every - 1, L, every)
        new_k = ks[idx].swapaxes(0, 1)
        new_v = vs[idx].swapaxes(0, 1)
        return h, (new_k, new_v), RecurrentState(ssm=ssm, conv=conv)

    # --- attention families (dense / moe / vlm / audio) ---
    windows = layer_windows(cfg)
    L = windows.shape[0]
    layer_idx = jnp.arange(L, dtype=jnp.int32)

    if cfg.encoder_layers:   # whisper decoder: self-attn + cross-attn
        x = x + params["dec_pos"][positions.astype(jnp.int32)].astype(x.dtype)

        def body(h, xs):
            lp, cp, w, li = xs
            h, k, v = _attn_layer_step(cfg, lp, h, kvcfg, paged, li, w, positions)
            # cross attention over encoder output (dense, non-paged)
            with jax.named_scope("attention"):
                hd = cfg.resolved_head_dim
                B = h.shape[0]
                hn = apply_norm(cfg.norm, cp["ln"], h)
                q = (hn @ cp["attn"]["wq"]).reshape(B, cfg.num_heads, hd)
                ck = (enc_out @ cp["attn"]["wk"]).reshape(
                    B, -1, cfg.num_kv_heads, hd)
                cv = (enc_out @ cp["attn"]["wv"]).reshape(
                    B, -1, cfg.num_kv_heads, hd)
                cattn = mea_attention(q[:, None], ck, cv, causal=False)[:, 0]
                h = h + out_project(cp["attn"], cattn[:, None])[:, 0]
            return h, (k, v)

        h, (ks, vs) = jax.lax.scan(
            body, x, (params["layers"], params["cross_layers"], windows,
                      layer_idx), unroll=L_unroll)
        return h, (ks.swapaxes(0, 1), vs.swapaxes(0, 1)), None

    def body(h, xs):
        lp, w, li = xs
        h, k, v = _attn_layer_step(cfg, lp, h, kvcfg, paged, li, w, positions)
        return h, (k, v)

    h, (ks, vs) = jax.lax.scan(body, x, (params["layers"], windows, layer_idx),
                               unroll=L_unroll)
    return h, (ks.swapaxes(0, 1), vs.swapaxes(0, 1)), None


def decode_logits(params: dict, cfg: ArchConfig, hidden: jnp.ndarray) -> jnp.ndarray:
    h = apply_norm(cfg.norm, params["final_norm"], hidden)
    if cfg.tie_embeddings:
        return unembed(params["embed"], h, tied=True)
    return unembed(params["unembed"], h, tied=False)

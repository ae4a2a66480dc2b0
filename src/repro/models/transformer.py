"""Config-driven unified LM covering all 10 assigned architectures.

One stack definition serves dense GQA transformers, MoE (DeepSeek MLA),
hybrid recurrent (RecurrentGemma), xLSTM, enc-dec audio (Whisper) and
VLM cross-attention (Llama-3.2-Vision).  Layers are grouped as
``prefix_layers`` (unrolled) + ``n_periods x period`` (scanned with
remat), so a 61-layer MoE lowers to one compact while loop.

Entry points (all pure functions of (params, batch)):
  * ``loss_fn``      — next-token CE (+ MoE aux, + MTP), for train_step
  * ``prefill``      — fills pre-allocated caches, returns last logits
  * ``decode_step``  — one token in, one token out, caches updated

The CiM context (the paper's approximate execution) threads through
every block; per-layer noise keys ride the layer scan.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import config as C
from .attention import (_use_cim_attn, attention_block, init_attention,
                        init_cache)
from .common import (CiMContext, CiMParams, Param, apply_mlp, apply_norm,
                     cim_linear, init_mlp, init_norm, param, unbox, wsc)
from .config import ModelConfig
from .mla import init_mla, init_mla_cache, mla_block
from .moe import init_moe, moe_block
from .rglru import init_rglru, init_rglru_cache, rglru_block
from .xlstm import (init_mlstm, init_mlstm_cache, init_slstm,
                    init_slstm_cache, mlstm_block, slstm_block)

DEC_CROSS = "dec_cross"   # whisper decoder layer: self + cross + mlp
ATTN_MOE = "attn_moe"     # attention + MoE FFN


def _next_token_nll(logits, tokens, offset: int):
    """Mean NLL of predicting tokens shifted by `offset`.

    Computed as logsumexp - (onehot contraction): no second (B, S, V)
    log-softmax tensor, and — unlike take_along_axis — the contraction
    stays vocab-sharded under GSPMD (a gather over the sharded V axis
    would all-gather the 152k-wide logits to every device)."""
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = tokens[:, offset:]
    v = logits.shape[-1]
    onehot = jax.nn.one_hot(tgt, v, dtype=logits.dtype)
    picked = jnp.einsum("bsv,bsv->bs", logits[:, :-offset].astype(jnp.float32),
                        onehot.astype(jnp.float32))
    return lse[:, :-offset] - picked


def sinusoidal_pos(positions, d):
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half) / half * jnp.log(10000.0))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# per-kind layer init
# ---------------------------------------------------------------------------


def _init_layer(key, kind: str, cfg: ModelConfig):
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p: Dict[str, Any] = {"norm1": init_norm(ks[0], d, cfg.norm)}
    needs_mlp = kind not in (C.MLSTM, C.SLSTM)
    if kind in (C.ATTN, C.LOCAL, C.ENC_ATTN, ATTN_MOE):
        if cfg.mla is not None:
            p["attn"] = init_mla(ks[1], d, cfg.n_heads, cfg.mla)
        else:
            p["attn"] = init_attention(ks[1], d, cfg.n_heads, cfg.n_kv_heads,
                                       cfg.head_dim_, cfg.qkv_bias,
                                       cfg.qk_norm)
    elif kind == C.CROSS:
        p["attn"] = init_attention(ks[1], d, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.head_dim_, cfg.qkv_bias, cfg.qk_norm)
        p["gate"] = param(ks[5], (1,), (None,), jnp.float32, init="zeros")
    elif kind == DEC_CROSS:
        p["attn"] = init_attention(ks[1], d, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.head_dim_, cfg.qkv_bias, cfg.qk_norm)
        p["norm_x"] = init_norm(ks[4], d, cfg.norm)
        p["xattn"] = init_attention(ks[5], d, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim_, cfg.qkv_bias, cfg.qk_norm)
    elif kind == C.RGLRU:
        p["rnn"] = init_rglru(ks[1], d, cfg.rnn.width or d,
                              cfg.rnn.conv_width)
    elif kind == C.MLSTM:
        p["rnn"] = init_mlstm(ks[1], d, cfg.n_heads)
    elif kind == C.SLSTM:
        p["rnn"] = init_slstm(ks[1], d, cfg.rnn.slstm_heads)
    else:
        raise ValueError(kind)
    if needs_mlp:
        p["norm2"] = init_norm(ks[2], d, cfg.norm)
        if kind == ATTN_MOE:
            p["moe"] = init_moe(ks[3], d, cfg.moe, cfg.act)
        else:
            p["mlp"] = init_mlp(ks[3], d, cfg.d_ff, cfg.act)
    return p


def _carries_kv(kind: str, cfg: ModelConfig) -> bool:
    """Does decode write this kind's KV in place into the stacked pool
    the layer scan carries (dense, non-window, non-MLA self-attention)?
    Every other kind's state is small and rides the scan's xs/ys."""
    return kind in (C.ATTN, ATTN_MOE) and cfg.mla is None


def kv_inplace_layers(cfg: ModelConfig) -> int:
    """Scanned body layers whose KV a compiled decode writes in place."""
    return cfg.n_periods * sum(_carries_kv(k, cfg) for k in cfg.period)


def decode_attn_kernel_layers(cfg: ModelConfig, cim: CiMParams,
                              platform: str) -> int:
    """Carried layers whose single-token decode attention runs
    `kernels.decode_attn` when the decode is lowered for `platform`
    with no ambient mesh (`attention.attention_block`'s routing): all
    of them on a TPU, unless the CiM attention kernels take attention
    (`cim`, the LM's params); none elsewhere."""
    if platform != "tpu" or _use_cim_attn(cim, False):
        return 0
    return kv_inplace_layers(cfg)


def _apply_layer(params, x, kind: str, cfg: ModelConfig, ctx: CiMContext,
                 positions, cache, x_aux, valid=None, append=False,
                 layer=None):
    """Returns (x, new_cache, aux_loss).  `valid` is the optional (B, S)
    ragged-batch mask (pad tokens excluded from self-attention KV; see
    attention_block) — only the self-attention kinds consume it.
    `append` routes the multi-token decode path (speculative verify):
    dense causal self-attention layers only.  `layer` indexes the
    stacked KV pool `cache` holds (`_carries_kv` kinds, decode only)."""
    aux = jnp.float32(0.0)
    h = apply_norm(params["norm1"], x, cfg.norm)
    new_cache = cache
    attn_kw = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                   head_dim=cfg.head_dim_, rope_fraction=cfg.rope_fraction,
                   rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm, ctx=ctx,
                   q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
                   positions=positions)
    if append and (kind not in (C.ATTN, ATTN_MOE) or cfg.mla is not None):
        raise ValueError(
            "multi-token (append) decode needs dense full-attention "
            f"layers with explicit positions; kind {kind!r} does not "
            "qualify")
    if kind in (C.ATTN, ATTN_MOE, C.LOCAL, C.ENC_ATTN):
        if cfg.mla is not None and kind in (C.ATTN, ATTN_MOE):
            a, new_cache = mla_block(params["attn"], h, n_heads=cfg.n_heads,
                                     mla=cfg.mla, ctx=ctx,
                                     rope_theta=cfg.rope_theta,
                                     q_chunk=cfg.attn_q_chunk,
                                     positions=positions, cache=cache)
        else:
            a, new_cache = attention_block(
                params["attn"], h,
                causal=(kind != C.ENC_ATTN),
                window=cfg.window if kind == C.LOCAL else None,
                cache=cache, valid=valid, append=append, layer=layer,
                **attn_kw)
        x = x + a
    elif kind == C.CROSS:
        a, new_cache = attention_block(params["attn"], h, causal=False,
                                       cache=cache, x_kv=x_aux,
                                       is_cross=True, **attn_kw)
        x = x + (jnp.tanh(params["gate"].value)
                 * a.astype(jnp.float32)).astype(x.dtype)
    elif kind == DEC_CROSS:
        sc = None if cache is None else cache["self"]
        a, c_self = attention_block(params["attn"], h, causal=True,
                                    cache=sc, valid=valid, **attn_kw)
        x = x + a
        h2 = apply_norm(params["norm_x"], x, cfg.norm)
        cc = None if cache is None else cache["cross"]
        a2, c_cross = attention_block(params["xattn"], h2, causal=False,
                                      cache=cc, x_kv=x_aux, is_cross=True,
                                      **attn_kw)
        x = x + a2
        new_cache = None if cache is None else {"self": c_self,
                                                "cross": c_cross}
    elif kind == C.RGLRU:
        a, new_cache = rglru_block(params["rnn"], h, ctx=ctx, cache=cache)
        x = x + a
    elif kind == C.MLSTM:
        a, new_cache = mlstm_block(params["rnn"], h, n_heads=cfg.n_heads,
                                   chunk=cfg.rnn.mlstm_chunk, ctx=ctx,
                                   cache=cache)
        return x + a, new_cache, aux
    elif kind == C.SLSTM:
        a, new_cache = slstm_block(params["rnn"], h,
                                   n_heads=cfg.rnn.slstm_heads, ctx=ctx,
                                   cache=cache)
        return x + a, new_cache, aux
    else:
        raise ValueError(kind)

    h = apply_norm(params["norm2"], x, cfg.norm)
    if kind == ATTN_MOE:
        m, aux = moe_block(params["moe"], h, moe=cfg.moe, act=cfg.act,
                           ctx=ctx)
    else:
        m = apply_mlp(params["mlp"], h, cfg.act, ctx)
    return x + m, new_cache, aux


def _kind_cache_spec(kind: str, cfg: ModelConfig):
    """Logical sharding specs mirroring `_init_kind_cache` (resolved with
    divisibility fallback by parallel/sharding.py): batch on the data
    axes, KV heads / latent / inner-state dims on the model axis."""
    attn = {"k": ("batch", None, "heads"),
            "v": ("batch", None, "heads"), "pos": None}
    if kind in (C.ATTN, ATTN_MOE):
        if cfg.mla is not None:
            # the latent is shared by all heads: sharding it on the model
            # axis conflicts with head-sharded q_lat (measured 8x peak
            # regression) — replicate over model, shard batch only
            return {"ckv": ("batch", None, None),
                    "kr": ("batch", None, None), "pos": None}
        return dict(attn)
    if kind in (C.LOCAL, C.CROSS):
        return dict(attn)
    if kind == DEC_CROSS:
        return {"self": dict(attn), "cross": dict(attn)}
    if kind == C.RGLRU:
        return {"h": ("batch", "ff"), "conv": ("batch", None, "ff"),
                "pos": None}
    if kind == C.MLSTM:
        return {"c": ("batch", None, None, "ff"),
                "n": ("batch", None, None), "m": ("batch", None),
                "pos": None}
    if kind == C.SLSTM:
        s = ("batch", None, None)
        return {"c": s, "n": s, "h": s, "m": s, "pos": None}
    raise ValueError(kind)


def cache_specs(cfg: ModelConfig):
    """Logical spec tree matching `LM.init_caches` (body specs get a
    leading None for the stacked layer axis)."""
    prefix = [_kind_cache_spec(k, cfg) for k in cfg.prefix_layers]
    body = None
    if cfg.n_periods:
        one = {str(i): _kind_cache_spec(k, cfg)
               for i, k in enumerate(cfg.period)}
        body = jax.tree_util.tree_map(
            lambda sp: (None,) + tuple(sp) if isinstance(sp, tuple) else
            (None,),
            one, is_leaf=lambda x: x is None or isinstance(x, tuple))
    return {"prefix": prefix, "body": body}


def _init_kind_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                     per_slot: bool = False):
    # THE ragged/per-slot gate: LM.prefill(lengths=...) allocates its
    # caches through here, so raising covers every ragged entry path.
    # MLA latents, ring-buffered LOCAL windows (a padded prompt longer
    # than the ring would keep pad K/V and drop real tokens in the
    # skv>t roll), recurrent and cross/encoder state all lack the
    # explicit per-slot position the slot-pool contract needs — reject
    # rather than silently corrupt.
    if per_slot and (cfg.mla is not None
                     or kind not in (C.ATTN, ATTN_MOE)):
        raise ValueError(
            "per-slot caches (ragged prefill / continuous batching) "
            "need every layer's state to carry an explicit, non-ring "
            f"position; kind {kind!r} does not")
    if kind in (C.ATTN, ATTN_MOE):
        if cfg.mla is not None:
            return init_mla_cache(batch, max_len, cfg.mla)
        return init_cache(batch, max_len, cfg.n_kv_heads, cfg.head_dim_,
                          per_slot=per_slot)
    if kind == C.LOCAL:
        return init_cache(batch, max_len, cfg.n_kv_heads, cfg.head_dim_,
                          window=cfg.window)
    if kind == C.CROSS:
        return init_cache(batch, cfg.vision.n_tokens, cfg.n_kv_heads,
                          cfg.head_dim_)
    if kind == DEC_CROSS:
        return {"self": init_cache(batch, max_len, cfg.n_kv_heads,
                                   cfg.head_dim_),
                "cross": init_cache(batch, cfg.encoder.n_frames,
                                    cfg.n_kv_heads, cfg.head_dim_)}
    if kind == C.RGLRU:
        return init_rglru_cache(batch, cfg.rnn.width or cfg.d_model,
                                cfg.rnn.conv_width)
    if kind == C.MLSTM:
        return init_mlstm_cache(batch, cfg.d_model, cfg.n_heads)
    if kind == C.SLSTM:
        return init_slstm_cache(batch, cfg.d_model, cfg.rnn.slstm_heads)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LM:
    cfg: ModelConfig

    def __post_init__(self):
        self.cim = CiMParams.from_config(self.cfg.cim)

    # ---- init -----------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        p: Dict[str, Any] = {
            "embed": param(ks[0], (cfg.vocab, cfg.d_model),
                           ("vocab", "embed"), scale=0.01),
            "final_norm": init_norm(ks[1], cfg.d_model, cfg.norm),
        }
        if not cfg.tie_embeddings:
            p["head"] = param(ks[2], (cfg.d_model, cfg.vocab),
                              ("embed", "vocab"), scale=0.01)
        if cfg.prefix_layers:
            pk = jax.random.split(ks[3], len(cfg.prefix_layers))
            p["prefix"] = [
                _init_layer(pk[i], kind, cfg)
                for i, kind in enumerate(cfg.prefix_layers)]
        if cfg.n_periods:
            bk = jax.random.split(ks[4], cfg.n_periods)

            def initp(k):
                kk = jax.random.split(k, len(cfg.period))
                return {str(i): _init_layer(kk[i], kind, cfg)
                        for i, kind in enumerate(cfg.period)}

            body = jax.vmap(initp)(bk)
            # stacked leaves carry a leading layer axis in their spec
            p["body"] = jax.tree_util.tree_map(
                lambda q: Param(q.value, ("layers",) + tuple(q.spec)),
                body, is_leaf=lambda q: isinstance(q, Param))
        if cfg.vision is not None:
            p["vision_proj"] = param(ks[5], (cfg.vision.d_vision, cfg.d_model),
                                     (None, "embed"))
        if cfg.encoder is not None:
            ek = jax.random.split(ks[6], cfg.encoder.n_layers + 1)

            def inite(k):
                return {"0": _init_layer(k, C.ENC_ATTN, cfg)}

            enc = jax.vmap(inite)(ek[:-1])
            p["encoder"] = jax.tree_util.tree_map(
                lambda q: Param(q.value, ("layers",) + tuple(q.spec)),
                enc, is_leaf=lambda q: isinstance(q, Param))
            p["enc_norm"] = init_norm(ek[-1], cfg.d_model, cfg.norm)
        if cfg.mtp_depth:
            p["mtp_proj"] = param(ks[7], (2 * cfg.d_model, cfg.d_model),
                                  (None, "embed"))
            p["mtp_block"] = _init_layer(ks[7], C.ATTN, cfg)
            p["mtp_norm"] = init_norm(ks[7], cfg.d_model, cfg.norm)
        return p

    # ---- helpers --------------------------------------------------------
    def _embed(self, params, tokens):
        # gather the FSDP shards of the table; keep the vocab (model) shards
        table = wsc(params["embed"].value, ("vocab", None))
        e = jnp.take(table, tokens, axis=0)
        if self.cfg.family == "audio":   # sinusoidal decoder positions
            s = tokens.shape[1]
            e = e + sinusoidal_pos(jnp.arange(s), self.cfg.d_model
                                   ).astype(e.dtype)
        return wsc(e, ("batch", None, None))

    def _logits(self, params, x):
        x = apply_norm(params["final_norm"], x, self.cfg.norm)
        # Explicitly all-gather the head's FSDP (d_model/data) shards before
        # the dot: otherwise GSPMD resolves the data-axis conflict (batch vs
        # d_model both on "data") by UN-sharding the batch — a 40 GB/device
        # partial-logits + all-reduce at train_4k.  Gathering the weight
        # moves ~d*V/model_parallel bytes instead (tens of MB).
        if self.cfg.tie_embeddings:
            w = wsc(params["embed"].value, ("vocab", None)).T
        else:
            w = wsc(params["head"].value, (None, "vocab"))
        out = x @ w
        # keep the (B, S, V) tensor sharded on batch x vocab
        return wsc(out, ("batch", None, "vocab"))

    def _encode(self, params, frames, key):
        """Whisper encoder over precomputed frame embeddings (stub front)."""
        cfg = self.cfg
        x = frames + sinusoidal_pos(jnp.arange(frames.shape[1]),
                                    cfg.d_model).astype(frames.dtype)
        nl = cfg.encoder.n_layers
        keys = (jax.random.split(key, nl) if key is not None
                else jnp.zeros((nl, 2), jnp.uint32))

        def step(carry, xs):
            lp, k = xs
            ctx = CiMContext(self.cim, k if key is not None else None)
            y, _, _ = _apply_layer(lp["0"], carry, C.ENC_ATTN, cfg, ctx,
                                   None, None, None)
            return y, None

        step = jax.checkpoint(step) if cfg.remat else step
        x, _ = jax.lax.scan(step, x, (params["encoder"], keys))
        return apply_norm(params["enc_norm"], x, cfg.norm)

    def _aux_stream(self, params, batch, key):
        from .common import fsdp_gather

        cfg = self.cfg
        if cfg.vision is not None:
            return batch["vision"].astype(jnp.bfloat16) @ \
                fsdp_gather(params["vision_proj"])
        if cfg.encoder is not None:
            return self._encode(params, batch["enc_frames"], key)
        return None

    def _run_stack(self, params, x, positions, caches, key, x_aux,
                   valid=None, append=False, advance=False):
        """Prefix (unrolled) + body (scanned).  caches: None for training,
        else {"prefix": [...], "body": stacked-pytree}.

        advance=True (decode): the body's `_carries_kv` pools ride the
        scan carry and each layer writes only its new rows into them in
        place; a donated pool then never leaves its buffer.  Other kinds,
        and a cache being built (prefill), go through the scan's xs/ys."""
        cfg = self.cfg
        aux_total = jnp.float32(0.0)
        new_prefix = []
        for i, kind in enumerate(cfg.prefix_layers):
            ctx = CiMContext(self.cim,
                             None if key is None else jax.random.fold_in(key, i))
            c = None if caches is None else caches["prefix"][i]
            x, c2, aux = _apply_layer(params["prefix"][i], x, kind, cfg, ctx,
                                      positions, c, x_aux, valid, append)
            new_prefix.append(c2)
            aux_total += aux
        new_body = None
        if cfg.n_periods:
            keys = (jax.random.split(jax.random.fold_in(key, 0x5EED), cfg.n_periods)
                    if key is not None else jnp.zeros((cfg.n_periods, 2),
                                                      jnp.uint32))
            body_caches = None if caches is None else caches["body"]
            pools = {}
            if advance:
                pools = {str(i): body_caches[str(i)]
                         for i, kind in enumerate(cfg.period)
                         if _carries_kv(kind, cfg)}
                body_caches = {i: c for i, c in body_caches.items()
                               if i not in pools}

            def step(carry, xs):
                h, pools = carry
                lp, k, li, cache_in = xs
                aux_l = jnp.float32(0.0)
                pools = dict(pools)
                cache_out = None if cache_in is None else dict(cache_in)
                for i, kind in enumerate(cfg.period):
                    ctx = CiMContext(
                        self.cim,
                        None if key is None else jax.random.fold_in(k, i))
                    pool = pools.get(str(i))
                    if pool is not None:
                        ci = dict(pool, pos=pool["pos"][li])
                        h, c2, aux = _apply_layer(lp[str(i)], h, kind, cfg,
                                                  ctx, positions, ci, x_aux,
                                                  valid, append, layer=li)
                        pools[str(i)] = dict(
                            c2, pos=pool["pos"].at[li].set(c2["pos"]))
                    else:
                        ci = None if cache_in is None else cache_in[str(i)]
                        h, c2, aux = _apply_layer(lp[str(i)], h, kind, cfg,
                                                  ctx, positions, ci, x_aux,
                                                  valid, append)
                        if cache_in is not None:
                            cache_out[str(i)] = c2
                    aux_l += aux
                return (h, pools), (cache_out, aux_l)

            # decode never differentiates: no remat around the carried pool
            step = jax.checkpoint(step) if cfg.remat and not advance else step
            xs = (params["body"], keys, jnp.arange(cfg.n_periods),
                  body_caches)
            # the scan body traces ONCE but executes n_periods times:
            # scale MAC attribution so trace-time capture (obs/energy)
            # charges the full stack, not one period
            from repro.core.approx_gemm import obs_mac_scale

            with obs_mac_scale(cfg.n_periods):
                (x, pools), (new_body, auxes) = jax.lax.scan(
                    step, (x, pools), xs)
            aux_total += auxes.sum()
            if advance:
                new_body = {str(i): pools.get(str(i), new_body.get(str(i)))
                            for i in range(len(cfg.period))}
        return x, {"prefix": new_prefix, "body": new_body}, aux_total

    # ---- training -------------------------------------------------------
    def loss_fn(self, params, batch, key=None):
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x = self._embed(params, tokens)
        x_aux = self._aux_stream(params, batch, key)
        x, _, aux = self._run_stack(params, x, positions, None, key, x_aux)
        logits = self._logits(params, x)
        nll = _next_token_nll(logits, tokens, 1)
        loss = nll.mean()
        metrics = {"nll": loss, "aux": aux}
        if cfg.mtp_depth and s > 2:
            # DeepSeek-V3-style MTP: one extra block predicts t+2
            from .common import fsdp_gather

            emb_next = self._embed(params, jnp.roll(tokens, -1, axis=1))
            h = jnp.concatenate(
                [apply_norm(params["mtp_norm"], x, cfg.norm), emb_next],
                axis=-1) @ fsdp_gather(params["mtp_proj"])
            ctx = CiMContext(self.cim, key)
            h, _, _ = _apply_layer(params["mtp_block"], h, C.ATTN, cfg, ctx,
                                   positions, None, None)
            logits2 = self._logits(params, h)
            nll2 = _next_token_nll(logits2, tokens, 2)
            loss = loss + 0.3 * nll2.mean()
            metrics["mtp_nll"] = nll2.mean()
        loss = loss + aux
        metrics["loss"] = loss
        return loss, metrics

    def forward_logits(self, params, batch, key=None):
        """Full-sequence logits (B, S, V) for one teacher-forced pass —
        the allocation evaluator's measurement surface (DESIGN.md §16):
        no loss reduction, no caches, same stack as `loss_fn`."""
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x = self._embed(params, tokens)
        x_aux = self._aux_stream(params, batch, key)
        x, _, _ = self._run_stack(params, x, positions, None, key, x_aux)
        return self._logits(params, x)

    # ---- serving --------------------------------------------------------
    def init_caches(self, batch: int, max_len: int,
                    per_slot: bool = False):
        cfg = self.cfg
        prefix = [_init_kind_cache(k, cfg, batch, max_len, per_slot)
                  for k in cfg.prefix_layers]
        body = None
        if cfg.n_periods:
            one = {str(i): _init_kind_cache(k, cfg, batch, max_len,
                                            per_slot)
                   for i, k in enumerate(cfg.period)}
            body = jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(l, (cfg.n_periods,) + l.shape),
                one)
        return {"prefix": prefix, "body": body}

    def prefill(self, params, batch, key=None):
        """Fill pre-allocated caches; return (last-token logits, caches).

        Ragged batches: pass ``batch["lengths"]`` ((B,) true prompt
        lengths) and optionally ``batch["pad"]`` ("right", the default,
        or "left").  Per-sequence positions and a validity mask keep pad
        tokens out of every attention window, the returned logits are
        taken at each sequence's *last real token*, and the caches carry
        a per-slot (B,) ``pos`` vector.  Decode continuation from a
        ragged prefill requires right padding: left padding leaves pad
        garbage at the head of the KV slots, which the per-slot decode
        mask cannot express, so ``pad="left"`` is scoring-only and
        returns ``caches=None`` (a decode attempt fails loudly instead
        of silently attending to pad K/V).
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        lengths = batch.get("lengths")
        caches = self.init_caches(b, batch.get("max_len", s),
                                  per_slot=lengths is not None)
        ar = jnp.arange(s)[None, :]
        if lengths is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
            valid = None
        else:
            lengths = jnp.asarray(lengths, jnp.int32)
            pad = batch.get("pad", "right")
            if pad == "right":
                positions = jnp.broadcast_to(jnp.arange(s), (b, s))
                valid = ar < lengths[:, None]
                last = lengths - 1
            elif pad == "left":
                off = (s - lengths)[:, None]
                valid = ar >= off
                positions = jnp.where(valid, ar - off, 0)
                last = jnp.full((b,), s - 1, jnp.int32)
            else:
                raise ValueError(f"pad must be 'left'/'right', got {pad!r}")
        x = self._embed(params, tokens)
        x_aux = self._aux_stream(params, batch, key)
        x, caches, _ = self._run_stack(params, x, positions, caches, key,
                                       x_aux, valid=valid)
        if lengths is None:
            logits = self._logits(params, x[:, -1:])
        else:
            # per-sequence last *real* token (not the pad tail)
            logits = self._logits(params, x[jnp.arange(b), last][:, None])
            if batch.get("pad", "right") == "left":
                caches = None          # scoring-only (see docstring)
        return logits, caches

    def decode_step(self, params, caches, tokens, pos, key=None):
        """tokens: (B, 1); pos: scalar int32 (lockstep: one absolute
        position shared by the batch) or (B,) int32 (slot pool: each
        sequence at its own position — pairs with per-slot caches)."""
        cfg = self.cfg
        b = tokens.shape[0]
        pos = jnp.asarray(pos, jnp.int32)
        positions = (pos[:, None] if pos.ndim
                     else jnp.full((b, 1), pos, jnp.int32))
        x = self._embed_decode(params, tokens, positions)
        x, caches, _ = self._run_stack(params, x, positions, caches, key,
                                       None, advance=True)
        return self._logits(params, x), caches

    def decode_multi(self, params, caches, tokens, pos, key=None):
        """Score K continuation tokens per sequence in ONE forward pass
        (the speculative-decoding verify lane, DESIGN.md §12).

        tokens: (B, K); pos: scalar int32 or (B,) int32 — the cache
        fill level, i.e. the absolute position of tokens[:, 0].
        Returns (logits (B, K, V), caches advanced by K).  logits[:, i]
        is the next-token distribution after tokens[:, :i+1], exactly
        what K sequential `decode_step` calls would produce — and with
        a per-token-quantized integer CiM mode, *bitwise* exactly
        (tests/test_spec_decode.py holds this to array equality).
        """
        b, kk = tokens.shape
        pos = jnp.asarray(pos, jnp.int32)
        off = jnp.arange(kk, dtype=jnp.int32)
        positions = (pos[:, None] + off[None, :] if pos.ndim
                     else jnp.broadcast_to(pos + off, (b, kk)))
        x = self._embed_decode(params, tokens, positions)
        x, caches, _ = self._run_stack(params, x, positions, caches, key,
                                       None, append=True, advance=True)
        return self._logits(params, x), caches

    def _embed_decode(self, params, tokens, positions):
        table = wsc(params["embed"].value, ("vocab", None))
        e = jnp.take(table, tokens, axis=0)
        if self.cfg.family == "audio":
            e = e + sinusoidal_pos(positions, self.cfg.d_model
                                   ).astype(e.dtype)
        return e


# ---------------------------------------------------------------------------
# analytic parameter counts (roofline MODEL_FLOPS)
# ---------------------------------------------------------------------------


def _layer_params(kind: str, cfg: ModelConfig, active: bool) -> int:
    d, ff = cfg.d_model, cfg.d_ff
    hd, h, kh = cfg.head_dim_, cfg.n_heads, cfg.n_kv_heads
    mlp = 3 * d * ff if cfg.act == "swiglu" else 2 * d * ff
    if cfg.mla is not None and kind in (C.ATTN, ATTN_MOE):
        m = cfg.mla
        qk = m.qk_nope_head_dim + m.qk_rope_head_dim
        attn = (d * m.q_lora_rank + m.q_lora_rank * h * qk
                if m.q_lora_rank else d * h * qk)
        attn += d * m.kv_lora_rank + d * m.qk_rope_head_dim
        attn += m.kv_lora_rank * h * (m.qk_nope_head_dim + m.v_head_dim)
        attn += h * m.v_head_dim * d
    else:
        attn = d * hd * (h + 2 * kh) + h * hd * d
    if kind in (C.ATTN, C.ENC_ATTN, C.LOCAL):
        return attn + mlp
    if kind == ATTN_MOE:
        e = cfg.moe
        n_e = (e.top_k + e.n_shared) if active else (e.n_routed + e.n_shared)
        return attn + d * e.n_routed + n_e * 3 * d * e.d_expert
    if kind == C.CROSS:
        return attn + mlp
    if kind == DEC_CROSS:
        return 2 * attn + mlp
    if kind == C.RGLRU:
        w = cfg.rnn.width or d
        return 2 * d * w + 2 * w * w + w * d + mlp
    if kind == C.MLSTM:
        di = 2 * d
        return d * 2 * di + 3 * di * di + di * d
    if kind == C.SLSTM:
        nh = cfg.rnn.slstm_heads
        dh = d // nh
        return d * 4 * d + nh * dh * 4 * dh + d * d
    raise ValueError(kind)


def count_params(cfg: ModelConfig, active: bool = False) -> int:
    total = cfg.vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    for kind in cfg.layer_pattern:
        total += _layer_params(kind, cfg, active)
    if cfg.encoder is not None:
        total += cfg.encoder.n_layers * _layer_params(C.ENC_ATTN, cfg, active)
    if cfg.vision is not None:
        total += cfg.vision.d_vision * cfg.d_model
    if cfg.mtp_depth:
        total += _layer_params(C.ATTN, cfg, active) + 2 * cfg.d_model ** 2
    return total

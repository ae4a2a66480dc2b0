"""Attention: blockwise (flash-style) training/prefill paths, windowed
local attention, cross-attention, and single-token decode against a KV
cache.  GQA/MQA via KV-head grouping; optional QKV bias (qwen2.5),
per-head q/k RMSNorm (qwen3), fractional RoPE (stablelm 0.25,
chatglm 0.5).

Memory: the (q_chunk x kv_chunk) score tile is the only quadratic
buffer; both chunk sizes come from the config so 32k prefill fits.
Local attention only visits the ``window // kv_chunk + 1`` KV chunks a
query chunk can see, so RG-LRU-style archs stay O(S * window).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .common import (CiMContext, Param, _ambient_mesh, apply_rope,
                     cim_linear, param, rms_norm, rope_tables)

NEG_INF = -1e30


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, qkv_bias: bool, qk_norm: bool,
                   dtype=jnp.bfloat16):
    ks = jax.random.split(key, 8)
    p = {
        "wq": param(ks[0], (d_model, n_heads, head_dim),
                    ("embed", "heads", None), dtype),
        "wk": param(ks[1], (d_model, n_kv_heads, head_dim),
                    ("embed", "heads", None), dtype),
        "wv": param(ks[2], (d_model, n_kv_heads, head_dim),
                    ("embed", "heads", None), dtype),
        "wo": param(ks[3], (n_heads, head_dim, d_model),
                    ("heads", None, "embed"), dtype),
    }
    if qkv_bias:
        p["bq"] = param(ks[4], (n_heads, head_dim), ("heads", None), dtype,
                        init="zeros")
        p["bk"] = param(ks[5], (n_kv_heads, head_dim), ("heads", None), dtype,
                        init="zeros")
        p["bv"] = param(ks[6], (n_kv_heads, head_dim), ("heads", None), dtype,
                        init="zeros")
    if qk_norm:
        p["q_norm"] = param(ks[7], (head_dim,), (None,), init="ones")
        p["k_norm"] = param(ks[7], (head_dim,), (None,), init="ones")
    return p


def _project_qkv(params, x, n_heads, n_kv_heads, head_dim, ctx: CiMContext,
                 rope, qk_norm: bool):
    b, s, d = x.shape
    wq = Param(params["wq"].value.reshape(d, n_heads * head_dim),
               ("embed", "heads"))
    wk = Param(params["wk"].value.reshape(d, n_kv_heads * head_dim),
               ("embed", "heads"))
    wv = Param(params["wv"].value.reshape(d, n_kv_heads * head_dim),
               ("embed", "heads"))
    q = cim_linear(x, wq, ctx, "wq").reshape(b, s, n_heads, head_dim)
    k = cim_linear(x, wk, ctx, "wk").reshape(b, s, n_kv_heads, head_dim)
    v = cim_linear(x, wv, ctx, "wv").reshape(b, s, n_kv_heads, head_dim)
    if "bq" in params:
        q = q + params["bq"].value
        k = k + params["bk"].value
        v = v + params["bv"].value
    if qk_norm:
        q = rms_norm(q, params["q_norm"].value)
        k = rms_norm(k, params["k_norm"].value)
    q = apply_rope(q, rope)
    k = apply_rope(k, rope)
    return q, k, v


def _out_proj(params, o, ctx: CiMContext):
    b, s, h, dd = o.shape
    wo = Param(params["wo"].value.reshape(h * dd, -1), ("heads", "embed"))
    return cim_linear(o.reshape(b, s, h * dd), wo, ctx, "wo")


def _chunked_attn(q, k, v, q_chunk: int, kv_chunk: int, causal: bool,
                  window: Optional[int], q_offset, kv_len_valid,
                  seq_info=None):
    """Online-softmax blockwise attention.

    q: (B, Sq, H, D); k, v: (B, Skv, KH, D).  q_offset: absolute position
    of q[0] (for causal/window masks against the kv axis).
    kv_len_valid: number of valid kv positions (decode: cache fill level).

    seq_info: optional (q_positions (B, Sq), kv_positions (B, Skv),
    kv_valid (B, Skv) bool) triple for ragged batches — per-sequence
    positions drive the causal/window masks and kv_valid masks pad
    tokens out, so left/right-padded prompts never attend to padding.
    When None the scalar-arange fast path below is taken (bit-identical
    to the pre-ragged behavior).
    """
    b, sq, h, dd = q.shape
    skv, kh = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kh
    qpos_arr = kpos_arr = kval_arr = None
    if seq_info is not None:
        qpos_arr, kpos_arr, kval_arr = seq_info
    # pad q to a chunk multiple, mirroring the KV axis below (a prime Sq,
    # e.g. a 1601-token stream, must NOT shrink the chunk to its largest
    # divisor = 1 row); per-query online softmax is independent of the q
    # chunking, so the sliced result is bit-identical to the unpadded one
    qc = min(q_chunk, sq)
    sq_out = sq
    pad_q = (-sq) % qc
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        if seq_info is not None:       # padded queries: position 0 (their
            qpos_arr = jnp.pad(qpos_arr, ((0, 0), (0, pad_q)))  # rows are
        sq += pad_q                    # sliced off the output below)
    # pad KV to a chunk multiple; padded positions are masked by
    # kv_len_valid below
    kc = min(kv_chunk, skv)
    pad_kv = (-skv) % kc
    if pad_kv:
        kv_len_valid = jnp.minimum(kv_len_valid, skv)
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        if seq_info is not None:       # padded keys: position 0, invalid
            kpos_arr = jnp.pad(kpos_arr, ((0, 0), (0, pad_kv)))
            kval_arr = jnp.pad(kval_arr, ((0, 0), (0, pad_kv)))
        skv += pad_kv
    nq, nk = sq // qc, skv // kc
    scale = 1.0 / (dd ** 0.5)

    qr = q.reshape(b, nq, qc, kh, g, dd)
    kr = k.reshape(b, nk, kc, kh, dd)
    vr = v.reshape(b, nk, kc, kh, dv)
    kv_pos = jnp.arange(skv).reshape(nk, kc)

    # local attention: only the last W kv chunks can be visible to a q
    # chunk (q_offset == 0 for training/prefill where Sq == Skv).  With
    # per-sequence positions the chunk-index arithmetic no longer holds,
    # so the ragged path visits every chunk (the window mask still
    # applies positionally).
    local = window is not None and causal and seq_info is None
    w_chunks = min(nk, (window + qc - 1) // kc + 1) if local else nk

    def q_step(_, qi):
        qb = qr[:, qi]                             # (b, qc, kh, g, dd)
        if seq_info is None:
            qpos = q_offset + qi * qc + jnp.arange(qc)
        else:
            qpos_b = jax.lax.dynamic_slice_in_dim(qpos_arr, qi * qc, qc, 1)

        def kv_step(carry, kj_rel):
            m, l, acc = carry
            if local:
                # chunk index qi owns kv chunks [qi*qc//kc - W + 1 .. ...]
                last = (qi * qc + qc - 1) // kc
                kj = jnp.maximum(last - (w_chunks - 1) + kj_rel, 0)
            else:
                kj = kj_rel
            kb = jax.lax.dynamic_index_in_dim(kr, kj, 1, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(vr, kj, 1, keepdims=False)
            s = jnp.einsum("bqkgd,bckd->bkgqc", qb.astype(jnp.float32),
                           kb.astype(jnp.float32)) * scale
            if seq_info is None:
                kp = jax.lax.dynamic_index_in_dim(kv_pos, kj, 0,
                                                  keepdims=False)
                mask = kp[None, :] <= qpos[:, None] if causal else \
                    jnp.ones((qc, kc), bool)
                if window is not None:
                    mask = mask & (kp[None, :] > qpos[:, None] - window)
                mask = mask & (kp[None, :] < kv_len_valid)
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            else:
                kp = jax.lax.dynamic_slice_in_dim(kpos_arr, kj * kc, kc, 1)
                kval = jax.lax.dynamic_slice_in_dim(kval_arr, kj * kc, kc,
                                                    1)
                mask = kval[:, None, :]            # (b, qc, kc) per-seq
                if causal:
                    mask = mask & (kp[:, None, :] <= qpos_b[:, :, None])
                if window is not None:
                    mask = mask & (kp[:, None, :]
                                   > qpos_b[:, :, None] - window)
                s = jnp.where(mask[:, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqc,bckd->bkgqd", p, vb.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kh, g, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, qc), jnp.float32)
        a0 = jnp.zeros((b, kh, g, qc, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(w_chunks))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, qc, kh * g, dv)
        return None, o

    _, chunks = jax.lax.scan(q_step, None, jnp.arange(nq))
    # chunks: (nq, b, qc, h, dv) -> (b, sq, h, dv); drop q padding
    return chunks.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dv)[:, :sq_out]


def _use_cim_attn(p, is_cross: bool) -> bool:
    """Route this SDPA through the fused CiM attention kernels?

    Integer modes only (float modes keep the XLA flash path), self-
    attention only, and never under an ambient mesh — the mesh lanes
    shard the projections but attention stays per-device (DESIGN.md
    §13 lists cross-attention / mesh as oracle-fallback geometries)."""
    return (getattr(p, "attn", False)
            and p.mode in ("hardware", "bit_exact")
            and not is_cross and _ambient_mesh() is None)


def _cim_sdpa(q, k, v, p, *, causal, window, qpos, kpos, kval):
    """SDPA through core.approx_gemm.cim_attention (DESIGN.md §13).

    q: (B, Sq, H, D) float; k/v: (B, Skv, KH, D); qpos (B, Sq),
    kpos (B, Skv) int32 positions, kval (B, Skv) validity.  Returns the
    f32 attention output, or None when the dispatch engine rejects the
    geometry (the caller keeps the float path — the engine raising is
    the documented fallback contract, not an error).  A routing refusal
    (`RoutingError`: no kernel serves the request on this backend) is
    not a geometry rejection and propagates.

    Per-head tier allocation (``p.attn_heads``: one family name per q
    head): K/V expand to the per-q-head MHA layout — bit-consistent with
    the grouped run because quantization scales are per-head — then each
    family's head subset runs one fused call and scatters back."""
    from repro.core.approx_gemm import (GemmParams, RoutingError,
                                        cim_attention)

    def gp_for(family):
        # per_token is a linear-layer activation-row contract; attention
        # scales are already per-(batch, head) = per-sequence, so the
        # batch-invariance the verify lane needs holds without it
        return GemmParams(family=family, bits=p.bits, mode=p.mode,
                          mu=p.mu, c0=p.c0, c1=p.c1,
                          compressor=p.compressor,
                          n_approx_cols=p.n_approx_cols)

    kw = dict(causal=causal, window=window, q_positions=qpos,
              kv_positions=kpos, kv_valid=kval)
    h, kh = q.shape[2], k.shape[2]
    heads = getattr(p, "attn_heads", None)
    if heads is not None and len(heads) != h:
        raise ValueError(
            f"attn_heads has {len(heads)} entries for {h} query heads")
    try:
        if heads is None:
            return cim_attention(q, k, v, gp_for(p.family), **kw)
        g = h // kh
        ke = jnp.repeat(k, g, axis=2)
        ve = jnp.repeat(v, g, axis=2)
        out = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
        for fam in dict.fromkeys(heads):
            idx = jnp.asarray([i for i, f in enumerate(heads) if f == fam])
            o = cim_attention(q[:, :, idx], ke[:, :, idx], ve[:, :, idx],
                              gp_for(fam), **kw)
            out = out.at[:, :, idx].set(o)
        return out
    except RoutingError:
        raise
    except ValueError:
        return None                    # unsupported geometry: float path


def attention_block(params, x, *, n_heads, n_kv_heads, head_dim,
                    rope_fraction, rope_theta, qk_norm, ctx: CiMContext,
                    causal: bool = True, window: Optional[int] = None,
                    q_chunk: int = 1024, kv_chunk: int = 1024,
                    positions=None, cache: Optional[dict] = None,
                    x_kv=None, is_cross: bool = False, valid=None,
                    append: bool = False, layer=None, slots=None):
    """Full attention sub-block (projections + SDPA [+ cache update]).

    Training: cache=None -> returns (y, None).  Prefill (S > 1) writes
    the prompt's K/V into `cache` from position 0, as whole rows: every
    row past the prompt is zero afterwards.  Decode: x is (B, 1, D) and
    cache is the running KV state.

    valid: optional (B, S) bool mask for ragged (padded) batches.  Pad
    tokens are masked out of the KV axis so no query attends to them,
    `positions` supplies the per-sequence causal/window coordinates, and
    a prefilled cache records a *per-slot* fill level (``pos`` becomes a
    (B,) vector — the slot-pool contract the serving engine relies on).
    Decode accepts either a scalar ``pos`` (lockstep batch) or a (B,)
    vector (continuous batching: every slot at its own position).

    append=True is the multi-token decode path (speculative-decoding
    verify, DESIGN.md §12): x is (B, K, D) with K tokens per sequence
    continuing from the cache fill level — K keys/values scatter in at
    pos..pos+K-1 and query i attends causally through position pos+i,
    exactly the KV view K sequential single-token steps would build.
    Dense causal attention only (no window ring, no cross stream).

    layer: the scanned body's stacked pool (DESIGN.md §10).
    ``cache["k"]``/``["v"]`` are then the (L, B, T, KH*D) buffers the
    layer scan carries, ``cache["pos"]`` is this layer's fill level, and
    only this layer's rows are written, in place.  Single-token decode
    lowered for a TPU then attends with `kernels.decode_attn`, which
    reads the layer where it lies up to each slot's fill level (float
    and surrogate modes, no ambient mesh); elsewhere attention reads the
    layer back.

    slots: (B,) the pool rows a prefill's batch rows fill (admission
    into a slot pool); a slot past the pool, the admission padding, is
    dropped.  None writes batch row b into pool row b.
    """
    b, s, d = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    rope = rope_tables(positions, head_dim, rope_fraction, rope_theta)
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim, ctx,
                           rope, qk_norm)
    if x_kv is not None:  # cross-attention: keys/values from the aux stream
        _, k, v = _project_qkv(params, x_kv, n_heads, n_kv_heads, head_dim,
                               ctx, None, qk_norm)

    # ragged self-attention: per-sequence positions + pad-validity mask
    # (cross streams keep the dense path — their kv axis is never padded
    # by the prompt scheduler)
    seq_info = None
    if valid is not None and x_kv is None and s > 1:
        seq_info = (positions, positions, valid)

    if cache is None or (s > 1 and not append):
        y = None
        if _use_cim_attn(ctx.p, is_cross or x_kv is not None):
            kva = valid if valid is not None else \
                jnp.ones(positions.shape, jnp.int32)
            y = _cim_sdpa(q, k, v, ctx.p, causal=causal, window=window,
                          qpos=positions, kpos=positions, kval=kva)
        if y is None:
            y = _chunked_attn(q, k, v, q_chunk, kv_chunk, causal, window,
                              q_offset=0, kv_len_valid=k.shape[1],
                              seq_info=seq_info)
        y = _out_proj(params, y.astype(x.dtype), ctx)
        if cache is None:
            return y, None
        return y, _prefill_cache(cache, k, v, valid, layer, slots)

    # caches store K/V flattened to (B, T, KH*D): the flat dim shards
    # cleanly on the model axis (KH alone rarely divides it), matching
    # the joint (kh x d) sharding GSPMD wants internally — with a 4-D
    # cache it inserted a full cache reshard EVERY decode step
    # (69 GB/token at llama-11B 32k, EXPERIMENTS.md §Perf)
    kh_d = n_kv_heads * head_dim
    # cache["pos"] is a scalar for lockstep batches (every sequence at
    # the same position) or a (B,) vector for slot-pool serving (each
    # slot at its own fill level); qpos is each query's position, (S,)
    # or (B, S), and every mask below broadcasts to (B, S, T)
    pos = cache["pos"]
    t = cache["k"].shape[-2]
    tpos = jnp.arange(t)
    per_slot = getattr(pos, "ndim", 0) > 0
    qpos = (pos[:, None] if per_slot else pos) + jnp.arange(s)
    if is_cross:
        # cross-attention decode: encoder KV is static (filled at prefill)
        ck, cv, new_cache = cache["k"], cache["v"], cache
        kv_ok = tpos < (pos[:, None, None] if per_slot else pos)
    else:
        if append and (window is not None or not causal):
            raise NotImplementedError(
                "append (multi-token) decode supports dense causal "
                "self-attention only")
        # per-slot rows are scattered: a row past max_len (an idle slot,
        # or a draft past its budget) is dropped, never clamped onto a
        # live row
        slot = qpos if per_slot else pos
        if window is not None:        # ring buffer for local attention
            slot = slot % t
        ck = _put_kv(cache["k"], k.reshape(b, s, kh_d).astype(
            cache["k"].dtype), slot, layer)
        cv = _put_kv(cache["v"], v.reshape(b, s, kh_d).astype(
            cache["v"].dtype), slot, layer)
        new_cache = {"k": ck, "v": cv, "pos": pos + s}
        if window is None:
            # query i sees through pos + i: K tokens appended at once
            # (speculative verify, later in-flight keys written but
            # weighted exactly 0) attend as K sequential steps would
            kv_ok = tpos <= qpos[..., None]
        else:
            # ring slot i was written `age` steps ago; valid iff among
            # the last min(pos+1, t) writes
            age = (slot[..., None] - tpos) % t
            kv_ok = age < jnp.minimum(qpos + 1, t)[..., None]

    single = not append and not is_cross and window is None
    if single and _use_cim_attn(ctx.p, is_cross):
        # dense decode: causal(qpos=pos) + fill-level validity reproduce
        # the kv_ok mask exactly; window-ring decode keeps the XLA path
        # (ring slot order scrambles the positional coordinates)
        def view(pool):
            return _layer_view(pool, layer).reshape(b, t, n_kv_heads,
                                                    head_dim)

        o = _cim_sdpa(q, view(ck), view(cv), ctx.p, causal=True,
                      window=None,
                      qpos=jnp.broadcast_to(qpos, (b, 1)).astype(jnp.int32),
                      kpos=jnp.broadcast_to(tpos, (b, t)).astype(jnp.int32),
                      kval=jnp.broadcast_to(kv_ok, (b, 1, t))[:, 0])
        if o is not None:
            return _out_proj(params, o.astype(x.dtype), ctx), new_cache

    def xla_sdpa():
        return _dense_sdpa(q, ck, cv, kv_ok, layer)

    if (single and layer is not None and not _use_cim_attn(ctx.p, is_cross)
            and _ambient_mesh() is None):
        o = _pool_decode_sdpa(q, ck, cv, layer, pos, xla_sdpa)
    else:
        o = xla_sdpa()
    y = _out_proj(params, o.astype(x.dtype), ctx)
    return y, new_cache


def _prefill_cache(cache, k, v, valid, layer, slots):
    """`cache` with a prompt's K/V (B, S, KH, D) written from position 0
    as whole rows (`_put_kv`), and its fill level."""
    b, skv = k.shape[:2]
    t = cache["k"].shape[-2]
    rows = []
    for new, pool in ((k, cache["k"]), (v, cache["v"])):
        new = new.reshape(b, skv, -1).astype(pool.dtype)
        if valid is not None:
            # zero the pad rows: entries at/past each row's fill level
            # stay zero, so a rolled-back cache (serving/spec.py) is
            # byte-identical to one that never drafted.  Attention never
            # reads them (kv_valid / fill-level masks), so logits are
            # unchanged.
            new = jnp.where(valid[:, :, None], new, 0)
        if skv > t:  # window ring buffer keeps the last t entries at p % t
            p0 = skv - t
            new = jnp.roll(new[:, p0:], p0 % t, axis=1)
        rows.append(jnp.pad(new, ((0, 0), (0, t - new.shape[1]), (0, 0))))
    if valid is None:
        pos = jnp.int32(skv)
    else:
        # per-slot fill level: pad tokens don't count (right-padded
        # prompts resume decoding at their true length; see
        # models/transformer.LM.prefill for the left-pad caveat)
        pos = valid.sum(axis=1).astype(jnp.int32)
        if slots is not None:
            pos = cache["pos"].at[slots].set(pos, mode="drop")
    return {"k": _put_kv(cache["k"], rows[0], 0, layer, slots),
            "v": _put_kv(cache["v"], rows[1], 0, layer, slots), "pos": pos}


def _dense_sdpa(q, ck, cv, mask, layer):
    """XLA softmax attention of q (B, S, H, D) over layer `layer` of the
    K/V caches (`_layer_view`), at the positions where `mask`
    (broadcastable to (B, S, T)) holds.

    bf16 einsums with f32 softmax: an f32 cast of a 32k cache would
    materialize (and reshard) the whole cache every step; XLA:CPU cannot
    *execute* bf16xbf16->f32 dots, and TPU MXUs accumulate bf16 dots in
    f32 internally anyway."""
    b, s, h, d = q.shape
    t = ck.shape[-2]
    kh = ck.shape[-1] // d
    ck4 = _layer_view(ck, layer).reshape(b, t, kh, d)
    cv4 = _layer_view(cv, layer).reshape(b, t, kh, d)
    qg = q.reshape(b, s, kh, h // kh, d).astype(ck.dtype)
    s_ = jnp.einsum("bqkgd,btkd->bkgqt", qg, ck4
                    ).astype(jnp.float32) / (d ** 0.5)
    s_ = jnp.where(jnp.broadcast_to(mask, (b, s, t))[:, None, None], s_,
                   NEG_INF)
    p = jax.nn.softmax(s_, axis=-1).astype(cv.dtype)
    o = jnp.einsum("bkgqt,btkd->bkgqd", p, cv4)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, s, h, d)


def _pool_decode_sdpa(q, ck, cv, layer, pos, xla_sdpa):
    """Decode attention over layer `layer` of the carried pools: the
    `kernels.decode_attn` Pallas kernel where the decode is lowered for
    a TPU (it reads the layer where it lies, up to each slot's fill
    level), `xla_sdpa()` on every other platform."""
    from repro.kernels.decode_attn import pool_decode_attention

    return jax.lax.platform_dependent(
        tpu=lambda: pool_decode_attention(q, ck, cv, layer, pos),
        default=xla_sdpa)


def _put_kv(pool, rows, slot, layer=None, dest=None):
    """The one write of K or V rows (B, S, KH*D) into a cache.

    `pool` is one layer's (B, T, KH*D) cache, or with a `layer` index
    the stacked (L, B, T, KH*D) body pool, written at that layer in
    place.  `slot` is where the rows land: a scalar (the whole batch at
    one position) or (B, S) per-slot indices, scattered with mode="drop"
    so a slot past T is dropped, never clamped onto a live row.  `dest`
    (B,) names the pool rows the batch fills with whole rows (S = T,
    slot 0); a dest past the pool is dropped the same way.  Without it
    batch row b lands in pool row b."""
    lead = () if layer is None else (layer,)
    if dest is not None:
        return pool.at[lead + (dest,)].set(rows, mode="drop")
    if getattr(slot, "ndim", 0):
        bidx = jnp.arange(rows.shape[0])[:, None]
        return pool.at[lead + (bidx, slot)].set(rows, mode="drop")
    return jax.lax.dynamic_update_slice(
        pool, rows.reshape((1,) * len(lead) + rows.shape),
        lead + (0, slot, 0))


def _layer_view(pool, layer):
    """One layer's (B, T, KH*D) cache out of `_put_kv`'s pool."""
    if layer is None:
        return pool
    return jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)


def init_cache(batch: int, max_len: int, n_kv_heads: int, head_dim: int,
               window: Optional[int] = None, dtype=jnp.bfloat16,
               per_slot: bool = False):
    """K/V stored flattened (B, T, KH*D) — see attention_block's decode
    path for why (joint kh x d sharding on the model axis).

    per_slot=True allocates a (B,) position vector instead of the scalar
    ``pos`` — the slot-pool layout: each batch row is an independent
    sequence at its own fill level (serving/engine.py)."""
    t = min(max_len, window) if window is not None else max_len
    return {
        "k": jnp.zeros((batch, t, n_kv_heads * head_dim), dtype),
        "v": jnp.zeros((batch, t, n_kv_heads * head_dim), dtype),
        "pos": jnp.zeros((batch,), jnp.int32) if per_slot
        else jnp.int32(0),
    }

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

from .common import (CiMContext, Param, apply_rope, cim_linear, param,
                     rms_norm, rope_tables)

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
    from .common import _ambient_mesh

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
                    append: bool = False, layer=None):
    """Full attention sub-block (projections + SDPA [+ cache update]).

    Training/prefill: cache=None -> returns (y, new_cache_or_None);
    prefill fills `cache` if one is passed (pre-allocated to max length).
    Decode: x is (B, 1, D) and cache is the running KV state.

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

    layer: decode into the scanned body's stacked pool (DESIGN.md §10).
    ``cache["k"]``/``["v"]`` are then the (L, B, T, KH*D) buffers the
    layer scan carries, ``cache["pos"]`` is this layer's fill level, and
    only the new rows are written, at ``layer``, in place; attention
    reads that layer back.  Dense non-window self-attention only.
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

    if cache is None:
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
        return _out_proj(params, y.astype(x.dtype), ctx), None

    # caches store K/V flattened to (B, T, KH*D): the flat dim shards
    # cleanly on the model axis (KH alone rarely divides it), matching
    # the joint (kh x d) sharding GSPMD wants internally — with a 4-D
    # cache it inserted a full cache reshard EVERY decode step
    # (69 GB/token at llama-11B 32k, EXPERIMENTS.md §Perf)
    kh_d = n_kv_heads * head_dim
    if append:
        # multi-token decode append (speculative verify).  Keys/values
        # for all K tokens scatter in at pos..pos+K-1; the per-query
        # causal mask `tpos <= pos + i` gives query i exactly the KV
        # window sequential decoding would have seen (later in-flight
        # keys are written but masked — a softmax weight of exactly 0).
        if is_cross or window is not None or not causal:
            raise NotImplementedError(
                "append (multi-token) decode supports dense causal "
                "self-attention only")
        pos = cache["pos"]
        t = cache["k"].shape[-2]
        per_slot = getattr(pos, "ndim", 0) > 0
        kf = k.reshape(b, s, kh_d).astype(cache["k"].dtype)
        vf = v.reshape(b, s, kh_d).astype(cache["v"].dtype)
        tpos = jnp.arange(t)
        off = jnp.arange(s)
        if per_slot:
            slot = pos[:, None] + off[None, :]            # (B, K)
            # past-max_len slots (a slot whose budget ends mid-draft)
            # are dropped by the scatter, never clamped onto live rows
            kv_ok = tpos[None, None, :] <= slot[:, :, None]   # (B, K, t)
            vmask = kv_ok[:, None, None, :, :]
        else:
            slot = pos
            qpos = pos + off
            kv_ok = tpos[None, :] <= qpos[:, None]            # (K, t)
            vmask = kv_ok[None, None, None, :, :]
        ck = _put_kv(cache["k"], kf, slot, layer)
        cv = _put_kv(cache["v"], vf, slot, layer)
        new_cache = {"k": ck, "v": cv, "pos": pos + s}
        kh = n_kv_heads
        g = n_heads // kh
        ck4 = _layer_view(ck, layer).reshape(b, t, kh, head_dim)
        cv4 = _layer_view(cv, layer).reshape(b, t, kh, head_dim)
        qg = q.reshape(b, s, kh, g, head_dim).astype(ck.dtype)
        s_ = jnp.einsum("bqkgd,btkd->bkgqt", qg, ck4
                        ).astype(jnp.float32) / (head_dim ** 0.5)
        s_ = jnp.where(vmask, s_, NEG_INF)
        p = jax.nn.softmax(s_, axis=-1).astype(cv.dtype)
        o = jnp.einsum("bkgqt,btkd->bkgqd", p, cv4)
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, s, n_heads, head_dim)
        return _out_proj(params, o.astype(x.dtype), ctx), new_cache

    if s > 1:  # prefill into a pre-allocated cache
        t = cache["k"].shape[1]
        skv = k.shape[1]
        kf = k.reshape(b, skv, kh_d)
        vf = v.reshape(b, skv, kh_d)
        if valid is not None:
            # zero the pad rows: entries at/past each row's fill level
            # stay zero, so a rolled-back cache (serving/spec.py) is
            # byte-identical to one that never drafted.  Attention never
            # reads them (kv_valid / fill-level masks), so logits are
            # unchanged.
            kf = jnp.where(valid[:, :, None], kf, 0)
            vf = jnp.where(valid[:, :, None], vf, 0)
        if skv <= t:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], kf.astype(cache["k"].dtype), (0, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], vf.astype(cache["v"].dtype), (0, 0, 0))
        else:  # window ring buffer keeps the last t entries at slot p % t
            p0 = skv - t
            ck = jnp.roll(kf[:, p0:].astype(cache["k"].dtype), p0 % t,
                          axis=1)
            cv = jnp.roll(vf[:, p0:].astype(cache["v"].dtype), p0 % t,
                          axis=1)
        y = None
        if _use_cim_attn(ctx.p, is_cross):
            kva = valid if valid is not None else \
                jnp.ones(positions.shape, jnp.int32)
            y = _cim_sdpa(q, k, v, ctx.p, causal=causal, window=window,
                          qpos=positions, kpos=positions, kval=kva)
        if y is None:
            y = _chunked_attn(q, k, v, q_chunk, kv_chunk, causal, window,
                              q_offset=0, kv_len_valid=k.shape[1],
                              seq_info=seq_info)
        if valid is not None:
            # per-slot fill level: pad tokens don't count (right-padded
            # prompts resume decoding at their true length; see
            # models/transformer.LM.prefill for the left-pad caveat)
            pos_out = valid.sum(axis=1).astype(jnp.int32)
        else:
            pos_out = jnp.int32(k.shape[1])
        new_cache = {"k": ck, "v": cv, "pos": pos_out}
        return _out_proj(params, y.astype(x.dtype), ctx), new_cache

    # single-token decode.  cache["pos"] is a scalar for lockstep batches
    # (every sequence at the same position) or a (B,) vector for slot-pool
    # serving (each slot at its own fill level); the vector path scatters
    # per-slot and builds a per-slot validity mask.
    pos = cache["pos"]
    t = cache["k"].shape[-2]
    per_slot = getattr(pos, "ndim", 0) > 0
    if not is_cross:
        if window is not None:        # ring buffer for local attention
            slot = pos % t
        else:
            slot = pos
        kf = k.reshape(b, 1, kh_d).astype(cache["k"].dtype)
        vf = v.reshape(b, 1, kh_d).astype(cache["v"].dtype)
        tpos = jnp.arange(t)
        if per_slot:
            if window is not None:
                age = (slot[:, None] - tpos[None, :]) % t
                kv_ok = age < jnp.minimum(pos + 1, t)[:, None]
            else:
                kv_ok = tpos[None, :] <= pos[:, None]          # (B, t)
            # out-of-range slots (an idle lane slot past max_len) are
            # dropped by the scatter, never clamped onto live entries
            slot = slot[:, None]
        else:
            if window is not None:
                # ring slot i was written `age` steps ago; valid iff among
                # the last min(pos+1, t) writes
                age = (slot - tpos) % t
                kv_ok = age < jnp.minimum(pos + 1, t)
            else:
                kv_ok = tpos <= pos
        ck = _put_kv(cache["k"], kf, slot, layer)
        cv = _put_kv(cache["v"], vf, slot, layer)
        new_cache = {"k": ck, "v": cv, "pos": pos + 1}
    else:
        # cross-attention decode: encoder KV is static (filled at prefill)
        ck, cv = cache["k"], cache["v"]
        if per_slot:
            kv_ok = jnp.arange(t)[None, :] < pos[:, None]
        else:
            kv_ok = jnp.arange(t) < pos
        new_cache = cache
    kh = n_kv_heads
    g = n_heads // kh
    # bf16 math with f32 accumulation: an f32 cast of the 32k cache would
    # materialize (and reshard) the whole cache every step
    ck4 = _layer_view(ck, layer).reshape(b, t, kh, head_dim)
    cv4 = _layer_view(cv, layer).reshape(b, t, kh, head_dim)
    if not is_cross and window is None and _use_cim_attn(ctx.p, is_cross):
        # dense decode: causal(qpos=pos) + fill-level validity reproduce
        # the kv_ok mask exactly; window-ring decode keeps the XLA path
        # (ring slot order scrambles the positional coordinates)
        qpos_d = pos[:, None].astype(jnp.int32) if per_slot else \
            jnp.full((b, 1), pos, jnp.int32)
        kpos_d = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        kval_d = kv_ok if kv_ok.ndim == 2 else \
            jnp.broadcast_to(kv_ok, (b, t))
        o = _cim_sdpa(q, ck4, cv4, ctx.p, causal=True, window=None,
                      qpos=qpos_d, kpos=kpos_d, kval=kval_d)
        if o is not None:
            return _out_proj(params, o.astype(x.dtype), ctx), new_cache
    qg = q.reshape(b, 1, kh, g, head_dim).astype(ck.dtype)
    # NB: bf16 einsums + f32 softmax — XLA:CPU cannot *execute*
    # bf16xbf16->f32 dots, and TPU MXUs accumulate bf16 dots in f32
    # internally anyway
    s_ = jnp.einsum("bqkgd,btkd->bkgqt", qg, ck4).astype(jnp.float32) \
        / (head_dim ** 0.5)
    vmask = (kv_ok[:, None, None, None, :] if kv_ok.ndim == 2
             else kv_ok[None, None, None, None, :])
    s_ = jnp.where(vmask, s_, NEG_INF)
    p = jax.nn.softmax(s_, axis=-1).astype(cv.dtype)
    o = jnp.einsum("bkgqt,btkd->bkgqd", p, cv4)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, 1, n_heads, head_dim)
    y = _out_proj(params, o.astype(x.dtype), ctx)
    return y, new_cache


def _put_kv(pool, rows, slot, layer=None):
    """Write new K or V rows (B, S, KH*D) into `pool` at fill level
    `slot`: a scalar (the whole batch at one position) or (B, S) per-slot
    indices, scattered with mode="drop" so a slot past T is dropped,
    never clamped onto a live row.  `pool` is one layer's (B, T, KH*D)
    cache, or with a `layer` index the stacked (L, B, T, KH*D) body pool,
    written at that layer in place."""
    if getattr(slot, "ndim", 0):
        bidx = jnp.arange(rows.shape[0])[:, None]
        idx = (bidx, slot) if layer is None else (layer, bidx, slot)
        return pool.at[idx].set(rows, mode="drop")
    if layer is None:
        return jax.lax.dynamic_update_slice(pool, rows, (0, slot, 0))
    return jax.lax.dynamic_update_slice(pool, rows[None],
                                        (layer, 0, slot, 0))


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

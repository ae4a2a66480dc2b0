"""Cross-tier speculative decoding: the accuracy ladder as a speed
ladder (DESIGN.md §12).

The drafter is *free*: the approximate tier IS the same model over the
same weights on the cheap datapath (a lane pick, not a second network).
Each spec round on the exact lane's slot pool (up to `rounds_per_call`
of them chain in one dispatch via an on-device while_loop — budget/EOS
bookkeeping is computable on device, so consecutive rounds run without
paying per-call overhead or a host round-trip between them, and the
loop exits early once every slot's budget is drained):

  1. **draft** — k greedy tokens per slot on the drafter tier, fused
     into ONE jitted ``lax.scan`` call (per-call dispatch overhead is
     what dominates small-model decode; k separate calls would cost
     more than they save).  The drafter writes its approximate K/V into
     the shared pool at [fill, fill+k) and the scan resets every
     ``pos`` leaf back to fill before returning — draft state is
     provisional by construction.
  2. **verify** — ONE batched multi-position pass on the verifier tier
     (``LM.decode_multi``) scores [t_last, d_1..d_k]: k+1 positions for
     the price of ~1 decode step, because the verifier runs per-token
     activation scales (``CiMConfig.per_token``), the quantization
     choice under which a (B, K) batch is bitwise equal to K sequential
     (B, 1) steps.  The verify pass overwrites the drafter's
     provisional K/V with exact entries at [fill, fill+k].
  3. **accept + roll back** — greedy targets g_i = argmax(verify
     logits); the agreeing prefix d_1..d_m (plus the bonus/correction
     token g_m) is emitted, truncated by the slot's remaining token
     budget and at its first EOS.  The cache is rolled back: the
     (k+1)-entry window at [new_fill, new_fill+k+1) is zeroed and every
     ``pos`` leaf set to new_fill — reusing the (B,) fill-level vector
     from the slot pool (PR 4).

**Bit-identity (the invariant the test suite pins):** every emitted
token is a verifier argmax given exact-cache context — accepted drafts
only because they EQUAL the verifier's argmax, the last token as the
verifier's own argmax where the draft diverged (or the bonus token).
By induction the emitted sequence is exactly what plain greedy decoding
on the verifier tier produces, whatever the drafter says; the drafter
only controls *throughput* (acceptance rate), never *output*.

**Cache invariant:** pool entries at positions >= fill are zero —
established at init (zeros), prefill (pad K/V zeroed, whole rows
written), decode (writes exactly at fill), and maintained by rollback.
It is what makes a rolled-back cache *byte-identical* to one that never
drafted, which the KV-rollback tests compare directly.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from .engine import LMLaneBackend


class SpecDecodeBackend(LMLaneBackend):
    """Slot-pool lane that decodes speculatively: drafter tier guesses,
    verifier tier (per-token exact) scores all guesses in one batched
    pass.  Prefill runs on the verifier (inherited), so admitted
    context is exact from the first token.

    `draft_ks` is the set of pre-warmed draft depths; `set_draft_k`
    switches between them without retracing (each depth owns its own
    pre-jitted draft/verify executables, keyed by the static k).

    `rounds_per_call` batches that many draft+verify rounds into one
    dispatch (budget/EOS bookkeeping threads on-device, so the rounds
    chain without host round-trips).  Emitted tokens are unchanged —
    it is pure dispatch amortization — but admission only happens
    between calls, so a queued request waits up to R-1 extra rounds
    for a free slot.  `rounds_per_call=1` restores per-round admission.

    `keep_logits=False` skips the per-call device→host transfer of the
    (B, R, k+1, V) verify-logits block (`last_spec_logits` stays None);
    engines that don't record logits should turn it off.
    """

    def __init__(self, lm, drafter_lm, params, *, draft_k: int = 4,
                 draft_ks: Optional[Sequence[int]] = None,
                 rounds_per_call: int = 4, keep_logits: bool = True,
                 **kw):
        if kw.get("mesh") is not None:
            raise ValueError(
                "speculative decoding does not support mesh serving: the "
                "verifier's per-token activation scales are row-local, "
                "which the shard_map dispatch path (global scales) "
                "cannot express")
        if not getattr(lm.cfg.cim, "per_token", False):
            raise ValueError(
                "spec-decode verifier needs per_token=True activation "
                "scales (tiers.spec_pair builds the right CiMConfig): "
                "batched verify is only bitwise equal to sequential "
                "decoding when each row's scale is its own")
        if rounds_per_call < 1:
            raise ValueError("rounds_per_call must be >= 1")
        super().__init__(lm, params, **kw)
        self.drafter_lm = drafter_lm
        self.rounds_per_call = int(rounds_per_call)
        self.keep_logits = bool(keep_logits)
        self.draft_ks = tuple(sorted(set(int(k) for k in
                                         (draft_ks or (draft_k,)))
                                     | {int(draft_k)}))
        if min(self.draft_ks) < 1:
            raise ValueError("draft depth must be >= 1")
        self.draft_k = int(draft_k)
        self._rounds: Dict[int, object] = {}
        for k in self.draft_ks:
            self._rounds[k] = self._make_round(k)
        self.last_spec_logits: Optional[np.ndarray] = None
        # acceptance telemetry (live slots only; warmup rounds are idle)
        self.n_rounds = 0
        self.n_drafted = 0
        self.n_accepted = 0
        self.n_emitted = 0

    # -- the jitted round --------------------------------------------------
    def _make_round(self, k: int):
        """ONE fused executable per draft depth: up to `rounds_per_call`
        draft+verify sub-rounds chained on-device (a while_loop that
        exits early once every slot's budget is drained), each k drafter
        steps (lax.scan) + the batched (k+1)-position verify + on-device
        acceptance + cache rollback.  A single dispatch per call —
        per-call overhead is what dominates small-batch decode, so
        neither the draft chain nor consecutive rounds may pay it
        per-step.  Budget/EOS bookkeeping is computable on device, so
        rounds chain without host round-trips: each sub-round decrements
        `remaining` by what it emitted and zeroes it at an emitted EOS,
        which is exactly the truncation the engine applies host-side.

        Returns (g (B, R, k+1) greedy targets, a (B, R) accepted
        counts, logits (B, R, k+1, V), caches, tok (B, 1), fill (B),
        n_exec — how many sub-rounds the loop actually ran).
        Unexecuted trailing rounds have a = 0 and zeroed buffers.
        Emitted tokens are g[s, r, :a_sr] in round order; a_sr =
        min(m_sr + 1, remaining_sr) truncated at the first EOS among
        them (m_sr = length of the agreeing draft prefix).  remaining=0
        marks an idle row: nothing is emitted and the rollback wipes
        the whole provisional window."""
        import jax
        import jax.numpy as jnp

        draft_step = self.drafter_lm.decode_step
        decode_multi = self.lm.decode_multi
        rounds = self.rounds_per_call

        def one_round(params, caches, tok, fill, remaining, eos):
            # -- draft: k greedy steps on the drafter tier, writing
            # provisional K/V at [fill, fill+k) (verify overwrites)
            def body(carry, _):
                c, t, p = carry
                lg, c = draft_step(params, c, t, p)
                nxt = jnp.argmax(lg[:, -1, :].astype(jnp.float32),
                                 axis=-1).astype(jnp.int32)
                return (c, nxt[:, None], p + 1), nxt

            (caches, _, _), drafts = jax.lax.scan(
                body, (caches, tok, fill), None, length=k)
            drafts = drafts.T                                  # (B, k)
            caches = _reset_pos(caches, fill)
            # -- verify: all k+1 positions in one batched pass on the
            # per-token exact tier
            toks = jnp.concatenate([tok, drafts], axis=1)      # (B, k+1)
            logits, caches = decode_multi(params, caches, toks, fill)
            g = jnp.argmax(logits.astype(jnp.float32),
                           axis=-1).astype(jnp.int32)          # (B, k+1)
            # -- accept the agreeing prefix (+ bonus/correction token)
            match = (drafts == g[:, :k]).astype(jnp.int32)     # (B, k)
            m = jnp.cumprod(match, axis=1).sum(axis=1)         # prefix len
            a = jnp.minimum(m + 1, remaining)
            is_eos = (g == eos[:, None]) & (eos[:, None] >= 0)
            eos_pos = jnp.argmax(is_eos, axis=1)               # first True
            has_eos = is_eos.any(axis=1)
            a = jnp.where(has_eos & (eos_pos < a), eos_pos + 1, a)
            caches = _rollback(caches, fill + a, k + 1)
            # -- thread slot state to the next sub-round: last emitted
            # token, advanced fill, decremented budget (0 after an
            # emitted EOS — the slot is done, later rounds idle)
            live = a > 0
            last = jnp.take_along_axis(g, jnp.maximum(a - 1, 0)[:, None],
                                       axis=1)                 # (B, 1)
            tok = jnp.where(live[:, None], last, tok)
            emitted_eos = (is_eos
                           & (jnp.arange(k + 1)[None, :] < a[:, None]))
            remaining = jnp.where(emitted_eos.any(axis=1), 0,
                                  remaining - a)
            return caches, tok, fill + a, remaining, g, a, logits

        vocab = self.lm.cfg.vocab

        def spec_call(params, caches, tok, fill, remaining, eos):
            # while_loop, not scan: the call EXITS EARLY once every slot
            # has drained its budget, so a large rounds_per_call never
            # burns draft+verify compute on an all-idle pool.  One round
            # always runs (r == 0) so an idle warmup call still
            # exercises + rolls back the provisional window.
            b = tok.shape[0]
            st = (jnp.int32(0), caches, tok, fill, remaining,
                  jnp.zeros((rounds, b, k + 1), jnp.int32),
                  jnp.zeros((rounds, b), jnp.int32),
                  jnp.zeros((rounds, b, k + 1, vocab), jnp.float32))

            def cond(st):
                return (st[0] == 0) | ((st[0] < rounds)
                                       & (st[4] > 0).any())

            def body(st):
                r, caches, tok, fill, remaining, g_b, a_b, l_b = st
                caches, tok, fill, remaining, g, a, logits = one_round(
                    params, caches, tok, fill, remaining, eos)
                return (r + 1, caches, tok, fill, remaining,
                        g_b.at[r].set(g), a_b.at[r].set(a),
                        l_b.at[r].set(logits.astype(jnp.float32)))

            n_exec, caches, tok, fill, _, g, a, logits = \
                jax.lax.while_loop(cond, body, st)
            return (jnp.moveaxis(g, 0, 1), a.T,
                    jnp.moveaxis(logits, 0, 1), caches, tok, fill,
                    n_exec)

        return jax.jit(spec_call, donate_argnums=(1,))

    # -- the spec round ----------------------------------------------------
    def set_draft_k(self, k: int) -> None:
        """Switch draft depth; only pre-warmed depths are allowed (an
        unwarmed depth would retrace mid-steady-state)."""
        if k not in self._rounds:
            raise ValueError(f"draft depth {k} was not pre-built; "
                             f"configured: {self.draft_ks}")
        self.draft_k = int(k)

    def spec_round(self, remaining: np.ndarray,
                   eos: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """`rounds_per_call` draft-k + verify rounds for the whole pool
        in ONE dispatch.

        `remaining[s]` is slot s's unfilled token budget (0 = idle row:
        rides along, emits nothing); `eos[s]` its EOS id or -1.  Returns
        (tokens (B, R, k+1), counts (B, R)): the engine emits
        tokens[s, r, :counts[s, r]] per slot, in round order.
        """
        jnp = self._jnp
        k = self.draft_k
        tok = jnp.asarray(self.slot_tokens[:, None], jnp.int32)
        fill = jnp.asarray(self.slot_pos, jnp.int32)
        with self._ctx():
            (g, a, logits, self.caches, tok_out, fill_out,
             n_exec) = self._rounds[k](
                self.params, self.caches, tok, fill,
                jnp.asarray(remaining, jnp.int32),
                jnp.asarray(eos, jnp.int32))
        g = np.asarray(g)                                  # (B, R, k+1)
        a = np.asarray(a, np.int64)                        # (B, R)
        self.last_spec_logits = (np.asarray(logits, np.float32)
                                 if self.keep_logits else None)
        self.slot_tokens = np.asarray(tok_out)[:, 0].astype(
            self.slot_tokens.dtype)
        self.slot_pos = np.asarray(fill_out).astype(self.slot_pos.dtype)
        live = a > 0
        self.n_rounds += int(n_exec)
        self.n_drafted += int(k * live.sum())
        self.n_accepted += int((a[live] - 1).sum())
        self.n_emitted += int(a.sum())
        return g, a

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verifier accepted."""
        return self.n_accepted / max(self.n_drafted, 1)

    @property
    def tokens_per_round(self) -> float:
        return self.n_emitted / max(self.n_rounds, 1)

    # -- warmup ------------------------------------------------------------
    def warmup(self) -> int:
        """Inherited warmup (prefill/plain decode/sampling), then
        one idle spec round per configured draft depth — so depth
        switches after warmup are dict lookups, never retraces.  The
        idle rounds leave no live state: remaining=0 everywhere means
        every rollback wipes its own provisional window (including the
        position-0 garbage the inherited warm decode writes)."""
        n = super().warmup()
        zero = np.zeros(self.n_slots, np.int64)
        none = np.full(self.n_slots, -1, np.int64)
        for k in self.draft_ks:
            self.draft_k = k
            self.spec_round(zero, none)
            self.slot_tokens[:] = 0
            self.slot_pos[:] = 0
            n += 1
        return n


# ---------------------------------------------------------------------------
# cache surgery
# ---------------------------------------------------------------------------
#
# The cache pytree is {"prefix": [per-layer dicts], "body": {kind-index:
# stacked layer dict}}; a positional KV cache is any {"k","v","pos"}
# subtree.  Prefix leaves are (B, t, d) / pos (B,); body leaves carry a
# leading scanned-layer dim: (L, B, t, d) / pos (L, B).  `_map_kv`
# recurses to every such subtree so the surgery is layout-agnostic.


def _is_kv(layer) -> bool:
    return isinstance(layer, dict) and "pos" in layer and "k" in layer


def _map_kv(tree, fn):
    if _is_kv(tree):
        return fn(tree)
    if isinstance(tree, dict):
        return {k: _map_kv(v, fn) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_map_kv(v, fn) for v in tree)
    return tree


def _reset_pos(caches, fill):
    """Set every positional-cache ``pos`` leaf to `fill` (broadcast over
    the body's stacked layer dim)."""
    import jax.numpy as jnp

    def fix(layer):
        p = layer["pos"]
        return {**layer,
                "pos": jnp.broadcast_to(fill.astype(p.dtype), p.shape)}

    return _map_kv(caches, fix)


def _rollback(caches, new_fill, width: int):
    """Roll the pool back to `new_fill`: zero the `width`-entry window
    at [new_fill, new_fill+width) in every K/V leaf and set every
    ``pos`` leaf to new_fill.

    The provisional window a spec round dirties is [old_fill,
    old_fill+width); since new_fill >= old_fill and entries >= old_fill
    were zero before the round (the cache invariant), zeroing the
    static-size window at new_fill restores "entries >= fill are zero"
    exactly — positions it touches beyond the dirty region were already
    zero.  mode="drop" discards out-of-range writes (slots near
    max_len), matching the scatter semantics of the decode paths.
    """
    import jax.numpy as jnp

    b = new_fill.shape[0]
    win = new_fill[:, None] + jnp.arange(width)            # (B, width)
    bidx = jnp.arange(b)[:, None]

    def fix(layer):
        k, v = layer["k"], layer["v"]
        if k.ndim == 4:                       # stacked body: (L, B, t, d)
            kz = k.at[:, bidx, win].set(0, mode="drop")
            vz = v.at[:, bidx, win].set(0, mode="drop")
        else:                                 # prefix layer: (B, t, d)
            kz = k.at[bidx, win].set(0, mode="drop")
            vz = v.at[bidx, win].set(0, mode="drop")
        return {**layer, "k": kz, "v": vz,
                "pos": jnp.broadcast_to(
                    new_fill.astype(layer["pos"].dtype),
                    layer["pos"].shape)}

    return _map_kv(caches, fix)

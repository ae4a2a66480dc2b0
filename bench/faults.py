"""Faults planted under a lane's timed path, to show that the comparison
of bench/correct.py catches them (bench/control.py on the chip, the
benchmark's tests on the CPU).  The benchmark's own runs plant none.

  altered     every served token is altered where it is sampled
  no_kv       decode writes no key or value for its own tokens (the
              cache's fill level still advances)
  wrong_slot  decode runs each slot's token against the next slot's
              key/value rows, reading them and writing its own key and
              value there (an off-by-one slot index)
"""

from __future__ import annotations

NAMES = ("altered", "no_kv", "wrong_slot")


def _is_kv(path) -> bool:
    return getattr(path[-1], "key", None) in ("k", "v")


def plant(backend, name: str) -> None:
    """Break one lane's timed path (`backend` is an LMLaneBackend)."""
    import jax
    import jax.numpy as jnp

    if name == "altered":
        greedy = backend._greedy

        def altered(logits):
            nxt, lg = greedy(logits)
            return (nxt + 1) % backend.lm.cfg.vocab, lg

        backend._greedy = altered
        return
    step = backend.lm.decode_step
    if name == "no_kv":
        def decode(p, c, tok, pos):
            logits, new = step(p, c, tok, pos)
            return logits, jax.tree_util.tree_map_with_path(
                lambda path, n, o: o if _is_kv(path) else n, new, c)
    elif name == "wrong_slot":
        def decode(p, c, tok, pos):
            logits, new = step(p, c, jnp.roll(tok, 1, 0), jnp.roll(pos, 1, 0))
            return jnp.roll(logits, -1, 0), new
    else:
        raise ValueError(f"unknown fault {name!r}; known: {NAMES}")
    backend._decode = jax.jit(decode, donate_argnums=(1,))

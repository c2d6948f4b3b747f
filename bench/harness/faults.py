"""Faults planted in the timed path.  Each must turn a run's ``correct``
false: the tests plant them at a size the CPU holds, and ``control.py``
reads them at the cell's own size.

An LM fault patches a built engine in place; a KAN fault wraps the
output of one ``runtime.execute`` call.
"""

from __future__ import annotations

import jax.numpy as jnp


def token_altered(engine) -> None:
    """Every fifth decode step emits the wrong token in each slot."""
    decode, n = engine.decode_active, [0]

    def step(tokens):
        n[0] += 1
        logits = decode(tokens)
        if n[0] % 5:
            return logits
        wrong = (jnp.argmax(logits, -1) + 7) % logits.shape[-1]
        return logits.at[jnp.arange(logits.shape[0]), wrong].set(1e4)

    engine.decode_active = step


def half_batch_left_out(engine) -> None:
    """Only the first half of the busy slots is computed; the others get
    the first busy slot's row."""
    decode = engine.decode_active

    def step(tokens):
        logits = decode(tokens)
        busy = [i for i, r in enumerate(engine.active) if r is not None]
        left_out = busy[(len(busy) + 1) // 2:]
        if not left_out:
            return logits
        return logits.at[jnp.asarray(left_out)].set(logits[busy[0]])

    engine.decode_active = step


def state_unchanged(engine) -> None:
    """The decode step returns the KV pool it was given."""
    inner = engine._decode

    def step(params, cache, *args):
        logits, _ = inner(params, cache, *args)
        return logits, cache

    engine._decode = step


def answer_altered(y):
    return y.at[3].add(1.0)


def half_rows_left_out(y):
    half = y.shape[0] // 2
    return y.at[half:].set(jnp.mean(y[:half], axis=0))


LM = {f.__name__: f for f in (token_altered, half_batch_left_out,
                               state_unchanged)}
KAN = {f.__name__: f for f in (answer_altered, half_rows_left_out)}

"""Device-side sampling for the serving engines.

The sampler is traced *inside* the engines' jitted prefill/decode steps,
so per-token logits never round-trip to the host — the only thing the
host sees each step is a ``(slots,)`` int32 array of sampled token ids.
The PRNG key is threaded through the step functions (split once per
step, new key returned alongside the tokens), which makes the
temperature path a pure function of the engine seed: two engines with
the same seed and the same schedule produce bitwise-identical token
streams, and — because the single-device and sharded engines share this
module and the same scheduler — the key stream is identical across
them, so device-count parity tests compare like with like.

Greedy (temperature <= 0) is a plain argmax in f32 — the same
tie-breaking (lowest index) as ``np.argmax`` on host, which is what
keeps batched-admit serving output token-identical to the original
host-sampling engine.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_sampler(temperature: float):
    """Returns ``sampler(logits, key) -> (tokens, new_key)``.

    logits: ``(B, V)`` any float dtype (cast to f32 for the math);
    tokens: ``(B,)`` int32. The key is split even on the greedy path so
    the key stream does not depend on the temperature setting.
    """
    greedy = temperature <= 0

    def sampler(logits: jax.Array, key: jax.Array):
        with jax.named_scope("sample"):
            key, sub = jax.random.split(key)
            lf = logits.astype(jnp.float32)
            if greedy:
                toks = jnp.argmax(lf, axis=-1)
            else:
                toks = jax.random.categorical(sub, lf / temperature, axis=-1)
            return toks.astype(jnp.int32), key

    return sampler

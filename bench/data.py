"""Inputs of the training cells, drawn from the run's seed.

The token draw is a copy of ``SyntheticLMData.host_batch`` in
``src/repro/data/pipeline.py`` (Zipf(1.3) marginals folded into the
vocabulary, one Philox stream per batch), kept here so that the yardstick
does not move when the program's pipeline does.  Encoder frames, where a
configuration has them, are drawn on the device in one jitted call.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def token_batch(seed: int, index: int, rows: int, seq: int, vocab: int) -> np.ndarray:
    key = (int(seed) << 32) ^ index
    gen = np.random.Generator(np.random.Philox(key=key))
    toks = gen.zipf(1.3, size=(rows, seq)).astype(np.int64) % vocab
    return toks.astype(np.int32)


def batch_pool(seed: int, n: int, rows: int, seq: int, vocab: int,
               enc_frames: int = 0, d_model: int = 0, sharding=None) -> List[Dict]:
    """``n`` batches of ``rows`` sequences, placed on the device.

    Every batch differs from every other; frames (if any) are bfloat16,
    the type the program computes in.
    """
    import jax
    import jax.numpy as jnp

    from bench.reference import seed_key

    pool = [{"tokens": jax.device_put(token_batch(seed, i, rows, seq, vocab), sharding)}
            for i in range(n)]
    if enc_frames:
        key = jax.random.fold_in(seed_key(seed), 0x0F)
        frames = jax.jit(
            lambda k: [jax.random.normal(jax.random.fold_in(k, i), (rows, enc_frames, d_model),
                                         jnp.bfloat16) for i in range(n)],
            out_shardings=[sharding] * n if sharding is not None else None,
        )(key)
        for b, f in zip(pool, frames):
            b["enc_frames"] = f
    return pool

"""What every configuration makes from the run's seed alike: PRNG keys
and the finetune job's token rows. Weights and adapters are the
architecture's own (the ``weights`` module a configuration names)."""

from __future__ import annotations

from typing import Dict

import jax
import numpy as np


def jax_key(seed: int, salt: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    word = np.random.default_rng([seed, salt]).integers(0, 2**31 - 1)
    return jax.random.PRNGKey(int(word))


def make_ft_data(vocab: int, n_stage: int, micro_batch: int, seq_len: int,
                 seed: int) -> Dict[str, np.ndarray]:
    """Zipf(1.2) token rows (as training/data.py draws them), one distinct
    micro-batch per stage; labels are the tokens (the loss shifts them)."""
    rng = np.random.default_rng([seed, 7])
    toks = np.minimum(rng.zipf(1.2, size=(n_stage, micro_batch, seq_len)),
                      vocab - 1).astype(np.int32)
    return {"tokens": toks, "labels": toks.copy()}

"""What every family's plain reference shares: the comparison that decides
``correct`` and the loss over a family's logits.  The architecture itself (the
forward in straightforward float32 ``jax.numpy``) is the family's own:
``benchmark/families/<model>.py``.

``correct`` compares logits (serve) or the loss (train), never sampled tokens:
with random weights the largest logit changes on rounding.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _xent(lg, targets):
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def loss_and_logits(logits: Callable, params: Dict[str, Any], config: Dict[str, Any], inputs: np.ndarray,
                    targets: np.ndarray, rows: Sequence[int]):
    """Mean next-token cross-entropy of a (B, T) batch under a family's
    ``logits(params, config, tokens, rows)``, a sequence at a time, and the
    first sequence's logits at the positions ``rows``: one forward a sequence
    serves both."""
    per_seq, picked = [], None
    for row_in, row_tg in zip(np.asarray(inputs), np.asarray(targets)):
        lg = logits(params, config, row_in, range(len(row_in)))
        if picked is None:
            picked = np.asarray(lg[jnp.asarray(np.asarray(rows, np.int32))])
        per_seq.append(float(_xent(lg, jnp.asarray(row_tg.astype(np.int32)))))
    return float(np.mean(per_seq)), picked


def rel_at_scale(got, want) -> float:
    """Largest difference as a share of the reference's largest magnitude."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) or 1.0))

"""How the check that decides ``correct`` judges served tokens and
logits against a reference's, whatever the model.

The plain reference of each model is its family's ``logits``
(``bench/families/<family>.py``); what is here does not depend on the
model.
"""

from __future__ import annotations

import numpy as np


def teacher_forced(prompts: np.ndarray, served: np.ndarray) -> np.ndarray:
    """Each prompt followed by all but the last served token: the
    sequence whose final ``G`` positions predicted the ``G`` served
    tokens."""
    return np.concatenate([prompts, served[:, :-1]], axis=1)


def widest_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Largest amount by which a token's reference logit lies below the
    reference's best at its position. (N, G, V) and (N, G)."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[..., None].astype(np.int64),
                             axis=-1)[..., 0]
    return float((best - got).max())


def widest_logit_error(ref_logits: np.ndarray, logits: np.ndarray) -> float:
    """Largest relative distance, over positions, between a position's
    logits and the reference's: ``|l - r| / |r|`` over the vocabulary.
    (N, G, V) both; infinite where a value is not finite."""
    err = (np.linalg.norm(logits - ref_logits, axis=-1)
           / np.linalg.norm(ref_logits, axis=-1))
    return float(err.max()) if np.isfinite(err).all() else float("inf")

"""How far a set of chosen ids lies off the scores it was chosen by.

For the reference module of a configuration whose layers choose (``run.py``
``reference_and_choices``): its ``logits_following`` takes the sets the
timed program chose on the compared rows and has to say, per row and site,
how far each lies off the reference's own float32 scores. The definition is
the benchmark's, so that every such module reads the same quantity:

  gap = (the largest score among the ids NOT in the set
         - the smallest score among those in it) / rms(scores - mean(scores))

the score being whatever the published top-k ranks by. It is negative where
the set is the reference's own top-k, just above nought where the program
swapped a near-tie, and of the order of 1 where it dropped a clearly better
id or chose at random. Nothing here knows what is chosen, or why.
"""

import jax.numpy as jnp
import numpy as np


def check_sets(choices, rounds: int, sizes) -> np.ndarray:
    """``choices`` as an int array ``[rounds, sites, k]``, or ``ValueError``:
    each set is ``k`` distinct ids, those of site ``s`` in ``range(sizes[s])``."""
    sets = np.asarray(choices)
    if sets.ndim != 3 or sets.shape[:2] != (rounds, len(sizes)) or sets.dtype.kind not in "iu":
        raise ValueError(
            f"choices are {sets.dtype} of shape {sets.shape}, not whole numbers of shape "
            f"[{rounds} rounds, {len(sizes)} sites, k]"
        )
    for row, site in np.ndindex(rounds, len(sizes)):
        ids = sets[row, site]
        if len(set(ids.tolist())) != len(ids) or ids.min() < 0 or ids.max() >= sizes[site]:
            raise ValueError(
                f"the set chosen at row {row} site {site} is {ids.tolist()}: not {len(ids)} "
                f"distinct ids of range({sizes[site]})"
            )
    return sets


def gaps(scores, chosen):
    """``scores``: ``[rows, n]`` float32, what the top-k ranks by; ``chosen``:
    ``[rows, k]`` ids. ``[rows]`` float32 gaps, as the module's text has them."""
    scores = jnp.asarray(scores, jnp.float32)
    inside = jnp.any(jnp.arange(scores.shape[-1])[None, None, :] == jnp.asarray(chosen)[:, :, None], axis=1)
    left_out = jnp.max(jnp.where(inside, -jnp.inf, scores), axis=-1)
    kept = jnp.min(jnp.where(inside, scores, jnp.inf), axis=-1)
    centred = scores - jnp.mean(scores, axis=-1, keepdims=True)
    return (left_out - kept) / jnp.sqrt(jnp.mean(centred * centred, axis=-1))

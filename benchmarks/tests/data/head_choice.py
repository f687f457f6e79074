"""A pair that follows a choice, for a program that has no router: the one
discrete choice a Llama-style toy makes is its head's, so ``choices`` reports
the two tokens a logits row puts first (one site, k = 2) and
``logits_following`` says how far that pair lies off the float32 reference's
own logits. It drives ``run.py``'s path for a configuration that names
``program.choices`` through a whole run on the CPU (``test_routed_reference.py``);
``choices`` stands in for the program's function, the rest is a reference
module as the contract has it."""

import numpy as np

import choice_gaps
from reference import logits  # noqa: F401 - a reference module exports it

CALLS = []  # the rows of every call of ``choices``, for the test
REPORT_THE_LAST = False  # the program at fault: it reports the two tokens a row puts LAST


def choices(harness, rows):
    if not (hasattr(harness, "wave") and hasattr(harness, "pool")):
        raise TypeError(f"choices is handed the harness, not {type(harness).__name__}")
    CALLS.append(len(rows))
    order = np.argsort(np.asarray(rows, np.float32), axis=-1)
    return (order[:, :2] if REPORT_THE_LAST else order[:, -2:])[:, None, :]


def logits_following(params, config, tokens, rounds, choices):
    ref = logits(params, config, tokens, rounds)
    sets = choice_gaps.check_sets(choices, rounds, [ref.shape[-1]])
    return ref, choice_gaps.gaps(ref, sets[:, 0])[:, None]

"""Faults planted in the program's population evaluator, to show that the
check of a run sees them: ``with planted(name): ...`` runs the enclosed
code with the fault in every evaluator of the process.

- ``answer_altered``: the first answer of every generation is moved by
  37.5 pp where it is produced.
- ``half_batch``: the second half of the folded batch's rows is replaced by
  the first half, so the counts are those of half the batch, taken as if
  they were the whole.

A step that returns its state unchanged and a lost exchange between chips
have no place in a one-chip search, which keeps no state across dispatches.
"""
import contextlib

import numpy as np


def _answer_altered(orig):
    def errors_once(self, allocs, params):
        errs = orig(self, allocs, params)
        errs[0] = (errs[0] + 37.5) % 100.0
        return errs
    return errors_once


def _half_batch(orig):
    def dispatch(self, params, banks, feats, labels, stack):
        n = feats.shape[0]
        idx = np.r_[np.arange(n // 2), np.arange(n - n // 2)]
        return orig(self, params, banks, feats[idx], labels[idx], stack)
    return dispatch


FAULTS = {"answer_altered": ("_errors_once", _answer_altered),
          "half_batch": ("_dispatch", _half_batch)}


@contextlib.contextmanager
def planted(fault: str):
    from repro.core.batched_eval import PopulationEvaluator
    name, make = FAULTS[fault]
    orig = getattr(PopulationEvaluator, name)
    setattr(PopulationEvaluator, name, make(orig))
    try:
        yield
    finally:
        setattr(PopulationEvaluator, name, orig)

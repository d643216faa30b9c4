"""`higgs_synth` with a drift along the table, as a fraud table drifts in
time: from the first row to the last the signal weakens (the logit's scale
falls from SCALE_FIRST to SCALE_LAST) and the positives thin out (its
offset falls from 0 to SHIFT_LAST). Any contiguous part of the table then
has a loss and a gradient of its own, so a trainer that leaves part of the
batch out no longer computes what the whole table gives. A table filled
apart (the validation rows) drifts over its own length."""

import functools

from benchmark.datasets import higgs_synth
from benchmark.datasets.higgs_synth import N_COLS, seed_key  # noqa: F401

SCALE_FIRST, SCALE_LAST, SHIFT_LAST = 2.0, 0.2, -3.0


def drift(position):
    return (SCALE_FIRST + (SCALE_LAST - SCALE_FIRST) * position,
            SHIFT_LAST * position)


fill = functools.partial(higgs_synth.fill, drift=drift)

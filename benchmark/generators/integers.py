"""``integers``: an independent uniform draw from ``[low, high)``, stored
as int32 — ``pinot_tpu/tools/ssb.py``'s ``rng.integers(low, high, n)`` as
data. dbgen draws ``lo_quantity`` (1-50), ``lo_discount`` (0-10), ``lo_tax``
(0-8) and the keys of a flat row this way. The domain is the range itself:
index = value - low.

What every file of ``benchmark/generators/`` gives (``harness/table.py``
reads a kind through these and through nothing else):

  draw(spec, rng, n)               the kind's own draws on the segment's
                                   generator; always made, so that a
                                   column does not depend on which others
                                   are asked for. Left out by a kind that
                                   draws nothing.
  column(spec, raw, cols, seed)    the column, from those draws, the
                                   columns drawn before it and the run's
                                   seed; made only where it is wanted.
  needs(spec)                      the earlier columns ``column`` reads;
                                   left out by a kind that reads none.
  check(spec)                      optional: whatever can be refused before
                                   a row is drawn.
  domain_size(spec)                how many distinct values it can take.
  value_of(spec, index)            domain index -> value, ascending.
  index_of(spec, values)           value -> domain index (int64).
"""

import numpy as np


def draw(spec, rng, n):
    return rng.integers(spec["low"], spec["high"], n)


def column(spec, raw, cols, seed):
    return raw.astype(np.int32)


def domain_size(spec):
    return spec["high"] - spec["low"]


def value_of(spec, index):
    return index + spec["low"]


def index_of(spec, values):
    return values.astype(np.int64) - spec["low"]

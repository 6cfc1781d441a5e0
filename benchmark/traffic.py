"""The one traffic generator.  A traffic mix is a file of parameters under
``benchmark/workloads/``; this module turns it and ``--seed`` into the host
arrays a cell feeds.  What the feed of a configuration looks like (which
arrays, which special ids) is the configuration's own: ``batch(cfg, traffic,
gen)`` in its reference file draws everything through the ``Generator`` here.
Every seed gets the same multiset of lengths in another order, so the work
of a run does not depend on the seed."""

from __future__ import annotations

import numpy as np

FIRST_WORD = 3   # ids below are reserved: <s>, <e>, <unk>


class Generator:
    """Seeded draws shared by every feed."""

    def __init__(self, seed: int):
        self.rng = np.random.default_rng([int(seed), 0x7AFF1C])

    def lengths(self, spec, rows: int, longest: int) -> np.ndarray:
        """``"full"``: every row ``longest``.  ``{"lo": a, "hi": b}``: the
        evenly spread set of ``rows`` lengths over [a, b], shuffled."""
        if spec == "full":
            return np.full((rows,), longest, np.int32)
        lens = np.round(np.linspace(spec["lo"], spec["hi"], rows))
        return self.rng.permutation(
            np.minimum(lens.astype(np.int32), longest))

    def ids(self, vocab: int, lengths: np.ndarray, width: int) -> np.ndarray:
        """[rows, width] word ids, zero past each row's length."""
        ids = self.rng.integers(FIRST_WORD, vocab, (len(lengths), width),
                                dtype=np.int32)
        live = np.arange(width)[None, :] < np.asarray(lengths)[:, None]
        return np.where(live, ids, 0)


def batches(ref, cfg: dict, traffic: dict, seed: int, n: int) -> list:
    """``n`` distinct feeds of the configuration ``ref`` describes."""
    gen = Generator(seed)
    return [ref.batch(cfg, traffic, gen) for _ in range(n)]

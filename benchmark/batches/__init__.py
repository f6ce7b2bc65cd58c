"""Kinds of batch, one module per (features, labels) pair, found by name:
``<features.kind>__<labels.kind>.py`` from the ``kind``s a configuration's
``features`` / ``labels`` blocks name (``cells.make_batches`` does the
lookup and seeds the generator). A module holds

    draw(rng, features, labels, n, batch, seq_len) -> [DataSet] * n

``rng`` is the seeded ``numpy.random.Generator`` and the only source of
randomness, ``features`` / ``labels`` the configuration's two blocks as they
are (after a rehearsal's overrides), ``seq_len`` the traffic's (``None`` where
it has none). It returns ``n`` distinct batches of ``batch`` examples as the
program's ``DataSet``s on the host, in the shapes and dtypes a user's iterator
would hand to ``fit``. A new kind is a new file here; no file that exists
changes.
"""
import numpy as np


def one_hot(ids, classes):
    """``ids`` [...] -> float32 [..., classes]."""
    out = np.zeros(ids.shape + (classes,), np.float32)
    np.put_along_axis(out.reshape(-1, classes), ids.reshape(-1, 1), 1.0,
                      axis=1)
    return out

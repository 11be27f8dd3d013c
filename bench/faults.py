"""Faults planted under the timed path, to show that `correct` fails.

Each takes ``patch(owner, name, value)`` (pytest's ``monkeypatch.setattr``
or `planted`'s own) and breaks the program the way a faulty change to it
could, while the harness runs as ever:

* ``token_altered``: every served token is the next id after the one the
  program picked;
* ``state_unchanged``: the decode step returns the cache it was given;
* ``half_batch``: the head gets the second half of the pooled batch
  zeroed, so those slots' tokens come from no hidden state.
"""

from __future__ import annotations

import contextlib

import numpy as np


def token_altered(patch):
    from repro.serving.engine import Engine
    pick = Engine._select_token
    patch(Engine, "_select_token",
          lambda self, row: (pick(self, row) + 1) % row.shape[-1])


def state_unchanged(patch):
    from repro.models import api
    step = api.decode_hidden
    patch(api, "decode_hidden",
          lambda p, cfg, c, t, pos: (step(p, cfg, c, t, pos)[0], c))


def half_batch(patch):
    from repro.serving.sparse_linear import SparseLinear
    apply = SparseLinear.apply

    def half(self, x, **kw):
        keep = (np.arange(x.shape[0]) < x.shape[0] // 2)[:, None, None]
        return apply(self, x * keep, **kw)
    patch(SparseLinear, "apply", half)


FAULTS = {f.__name__: f for f in (token_altered, state_unchanged,
                                  half_batch)}


@contextlib.contextmanager
def planted(name: str | None):
    """The program with fault ``name`` planted inside the block (none for
    ``None``), and restored after it."""
    undo = []

    def patch(owner, attr, value):
        undo.append((owner, attr, owner.__dict__[attr]))
        setattr(owner, attr, value)
    try:
        if name is not None:
            FAULTS[name](patch)
        yield
    finally:
        for owner, attr, old in reversed(undo):
            setattr(owner, attr, old)

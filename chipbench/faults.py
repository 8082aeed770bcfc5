"""Faults planted under the timed path, each a way a serving cell can go
wrong that the check has to catch: a step that returns its state
unchanged, half of the batch left out, a token altered where it is
produced. (A one-chip cell has no exchange between chips to leave out.)

``plant(name)`` replaces one method of the program's ``ServeEngine`` and
returns the function that puts it back. ``chipbench/tests/test_faults.py``
plants each under a whole CPU run at smoke size; ``control.py --fault``
plants one on the chip at the cell's own size."""
from __future__ import annotations

from typing import Callable

import numpy as np


def _state_unchanged(orig):
    def step(self, batch):
        logits, _ = orig(self, batch)
        return logits, self._state
    return step


def _half_batch_left_out(orig):
    def step(self, batch):
        toks = np.asarray(batch["tokens"]).copy()
        toks[1::2] = 0
        return orig(self, {**batch, "tokens": toks})
    return step


def _token_altered(orig):
    def emit(self, i, g, row, finished):
        orig(self, i, g, row, finished)
        g.tokens[-1] = (g.tokens[-1] + 1) % row.shape[-1]
    return emit


FAULTS = {
    "state_unchanged": ("_execute_step", _state_unchanged),
    "half_batch_left_out": ("_execute_step", _half_batch_left_out),
    "token_altered": ("_emit_token", _token_altered),
}


def plant(name: str) -> Callable[[], None]:
    """Plant the named fault; returns the undo."""
    from repro.serve.engine import ServeEngine
    attr, make = FAULTS[name]
    orig = getattr(ServeEngine, attr)
    setattr(ServeEngine, attr, make(orig))
    return lambda: setattr(ServeEngine, attr, orig)

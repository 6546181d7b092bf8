"""The closed loop that the window runs, and the end-to-end statistics
taken from it by the host clock."""

from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass

import numpy as np


@dataclass
class Window:
    step_s: list[float]  # each step's wall time, dispatch to blocked
    wall_s: float        # the window's wall time, first dispatch to last block

    @property
    def steps(self) -> int:
        return len(self.step_s)


def no_span(_name):
    return nullcontext()


def closed_loop(dispatch, *, seconds: float | None = None, steps: int | None = None,
                min_steps: int = 1, span=no_span) -> Window:
    """Run ``dispatch(i)`` and block on what it returns, one step after the
    other: ``steps`` of them, or as many as end within ``seconds`` (at
    least ``min_steps``).  Every step ends in ``block_until_ready``."""
    import jax

    if (seconds is None) == (steps is None):
        raise ValueError("give seconds or steps")
    times = []
    with span("bench.window"):
        start = time.perf_counter()
        end = start
        i = 0
        while True:
            t0 = time.perf_counter()
            with span("bench.dispatch"):
                out = dispatch(i)
            with span("bench.block"):
                jax.block_until_ready(out)
            end = time.perf_counter()
            times.append(end - t0)
            i += 1
            if steps is not None:
                if i >= steps:
                    break
            elif i >= min_steps and end - start >= seconds:
                break
    return Window(times, end - start)


def step_ms(w: Window) -> float:
    """The window's wall time over the steps completed in it."""
    return 1e3 * w.wall_s / w.steps


def step_p90_ms(w: Window) -> float:
    """The 90th percentile of the per-step wall times (linear interpolation)."""
    return 1e3 * float(np.percentile(w.step_s, 90))

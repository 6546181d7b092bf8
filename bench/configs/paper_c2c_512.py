"""Plain reference for ``paper_c2c_512``: the forward 3-D DFT in float64
(``jnp.fft`` convention: forward unnormalized, backward 1/n).  It imports
nothing of the program."""

from __future__ import annotations

import os

import numpy as np
import scipy.fft


def forward(x: np.ndarray) -> np.ndarray:
    return scipy.fft.fftn(x.astype(np.complex128), workers=os.cpu_count())

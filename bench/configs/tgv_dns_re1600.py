"""Plain reference for ``tgv_dns_re1600``: one RK2 step of the dealiased
pseudo-spectral Navier-Stokes equations in float64, one field at a time.
It imports nothing of the program.

State: the retained Fourier coefficients ``u_hat`` of shape
``(3, N, N, N//2 + 1)``, true coefficients (``rfftn / M**3``) of the
velocity on the padded ``M = 3N/2`` grid; axes 0 and 1 keep the
wavenumbers ``0..N/2-1, -N/2..-1`` (fft order), axis 2 keeps ``0..N/2``.

    du/dt = P[-(u . grad) u] - nu k^2 u_hat

with the product formed on the padded grid (3/2-rule dealiasing), the
rows ``kx = -N/2`` and ``ky = -N/2`` of the convective term zeroed (they
have no Hermitian partner among the retained modes), ``P`` the Leray
projection, and Heun's RK2: ``k1 = rhs(u)``, ``k2 = rhs(u + dt k1)``,
``u' = P(u + dt/2 (k1 + k2))``.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.fft

#: the three components of the convective term are formed in parallel
#: threads (numpy and scipy release the GIL), each FFT on a third of the
#: host's cores
_THREADS = 3
_WORKERS = max(1, (os.cpu_count() or 1) // _THREADS)


def wavenumbers(n: int):
    k = np.fft.fftfreq(n, 1.0 / n)
    return k[:, None, None], k[None, :, None], np.arange(n // 2 + 1, dtype=np.float64)[None, None, :]


def _padded(c: np.ndarray, n: int, m: int) -> np.ndarray:
    """Zero-pad retained modes into the ``(m, m, m//2+1)`` rfft layout."""
    h = n // 2
    full = np.zeros((m, m, m // 2 + 1), np.complex128)
    kz = n // 2 + 1
    full[:h, :h, :kz] = c[:h, :h]
    full[:h, m - h:, :kz] = c[:h, h:]
    full[m - h:, :h, :kz] = c[h:, :h]
    full[m - h:, m - h:, :kz] = c[h:, h:]
    return full


def backward(c: np.ndarray, n: int, m: int) -> np.ndarray:
    """Retained coefficients -> real field on the padded grid."""
    return scipy.fft.irfftn(_padded(c * float(m) ** 3, n, m), s=(m, m, m),
                            workers=_WORKERS, overwrite_x=True)


def forward(u: np.ndarray, n: int, m: int) -> np.ndarray:
    """Real field on the padded grid -> retained coefficients."""
    f = scipy.fft.rfftn(u, workers=_WORKERS)
    h = n // 2
    rows = np.r_[0:h, m - h:m]
    return f[np.ix_(rows, rows, np.arange(n // 2 + 1))] / float(m) ** 3


def project(v, kx, ky, kz):
    k2 = kx**2 + ky**2 + kz**2
    k2[k2 == 0] = 1.0
    div = (kx * v[0] + ky * v[1] + kz * v[2]) / k2
    return np.stack([v[0] - kx * div, v[1] - ky * div, v[2] - kz * div])


def rhs(u_hat: np.ndarray, n: int, m: int, nu: float) -> np.ndarray:
    kx, ky, kz = wavenumbers(n)
    ks = (kx, ky, kz)
    herm = ((kx != -n // 2) & (ky != -n // 2)).astype(np.float64)

    def convective(i):  # the retained coefficients of u_j d_j u_i
        acc = np.zeros((m, m, m))
        for j in range(3):
            g = backward(1j * ks[j] * u_hat[i], n, m)
            acc += np.multiply(g, u[j], out=g)
        return forward(acc, n, m) * herm

    with ThreadPoolExecutor(_THREADS) as pool:
        u = list(pool.map(lambda j: backward(u_hat[j], n, m), range(3)))
        conv_hat = np.stack(list(pool.map(convective, range(3))))
    del u
    k2 = kx**2 + ky**2 + kz**2
    return project(-conv_hat, kx, ky, kz) - nu * k2 * u_hat


def rk2_step(u_hat: np.ndarray, n: int, m: int, nu: float, dt: float) -> np.ndarray:
    u_hat = u_hat.astype(np.complex128)
    k1 = rhs(u_hat, n, m, nu)
    k2 = rhs(u_hat + dt * k1, n, m, nu)
    kx, ky, kz = wavenumbers(n)
    return project(u_hat + 0.5 * dt * (k1 + k2), kx, ky, kz)

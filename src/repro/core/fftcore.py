"""Local (serial) transform dispatch — the paper's ``seqxfftn``, generalized.

The paper assumes a vendor serial FFT (FFTW/MKL/ESSL) and promises the
machinery applies to "Fourier (or similar) transforms".  This module is
where that generality lives: a per-axis :class:`TransformSpec` describes
*which* 1-D transform each axis gets, and :func:`local_transform` executes
one stage of it in either direction.

Supported kinds (P3DFFT ships pruned/real transforms as first-class plan
options; FLUPS shows per-axis flexibility is what opens new solver
workloads):

``c2c``            — complex FFT/iFFT (``jnp.fft`` convention: forward
                     unnormalized, backward 1/n).
``r2c``            — real-input FFT, Hermitian-reduced to ``n//2+1`` bins;
                     backward is ``irfft(n=...)``.
``dct`` (II / III) — cosine transform via the FFT-based even/odd extension
                     trick (Makhoul), scipy's unnormalized convention;
                     backward is the exact inverse.  Real-to-real: applied
                     to a complex block it transforms re/im independently.
``dst`` (II / III) — sine transform, reduced to the DCT by
                     ``DST-II(x) = reverse(DCT-II((-1)^j x))``.
``pruned`` / ``n_keep`` — truncated spectrum: the forward transform keeps
                     only ``n_keep`` retained modes (centered ±k/2 split
                     for c2c, the leading bins for r2c); backward
                     zero-scatters them back before the inverse transform
                     (an r2c axis's zeros are built into its c2r spectrum).
                     With ``n = 3·n_keep/2`` this is exactly the 3/2-rule
                     dealiased transform of pseudo-spectral solvers.

Local FFT "vendors":

``impl="jnp"``     — ``jnp.fft`` (XLA FFT HLO).  Reference path; used for
                     oracles and the CPU container.
``impl="matmul"``  — four-step matmul DFT on the MXU via the Pallas kernel
                     in ``repro.kernels.fft``; DCT/DST axes run as a single
                     transform-matrix matmul (``dct_matmul``/``dst_matmul``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import spans

FORWARD = -1
BACKWARD = +1

_KINDS = ("c2c", "r2c", "dct", "dst")


@dataclass(frozen=True)
class TransformSpec:
    """One axis's 1-D transform.

    ``kind``      — "c2c" | "r2c" | "dct" | "dst".
    ``trig_type`` — 2 or 3 (dct/dst only; the forward type — backward is
                    its exact inverse).
    ``n_keep``    — retained spectral modes (c2c/r2c only); ``None`` keeps
                    the full spectrum.
    """

    kind: str = "c2c"
    trig_type: int = 2
    n_keep: int | None = None

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"unknown transform kind {self.kind!r}")
        if self.kind in ("dct", "dst") and self.trig_type not in (2, 3):
            raise ValueError(f"{self.kind} type must be 2 or 3, got {self.trig_type}")
        if self.n_keep is not None:
            if self.kind in ("dct", "dst"):
                raise ValueError("n_keep (pruning) applies to c2c/r2c axes only")
            if self.n_keep < 1:
                raise ValueError(f"n_keep must be >= 1, got {self.n_keep}")

    # -- factories ----------------------------------------------------------

    @staticmethod
    def c2c(n_keep: int | None = None) -> "TransformSpec":
        return TransformSpec("c2c", n_keep=n_keep)

    @staticmethod
    def r2c(n_keep: int | None = None) -> "TransformSpec":
        return TransformSpec("r2c", n_keep=n_keep)

    @staticmethod
    def dct(trig_type: int = 2) -> "TransformSpec":
        return TransformSpec("dct", trig_type=trig_type)

    @staticmethod
    def dst(trig_type: int = 2) -> "TransformSpec":
        return TransformSpec("dst", trig_type=trig_type)

    @staticmethod
    def pruned(n_keep: int) -> "TransformSpec":
        """Truncated complex spectrum (centered keep): with a grid of
        ``n = 3*n_keep//2`` points this is the 3/2-rule dealiased axis.

        Note (even ``n_keep`` in a plan with an r2c axis): the kept set
        {-n_keep/2, …, n_keep/2-1} is not symmetric — the -n_keep/2 mode
        has no +n_keep/2 partner, so the irfft's Hermitian projection
        halves its kz=0-plane content per round trip.  Valid spectra keep
        that row zero (what dealiased pseudo-spectral solvers do anyway;
        mpi4py-fft's padded transforms share this convention)."""
        return TransformSpec("c2c", n_keep=n_keep)

    # -- properties ---------------------------------------------------------

    @property
    def real_to_real(self) -> bool:
        """Transform maps real -> real (complex blocks: re/im separately)."""
        return self.kind in ("dct", "dst")

    def spectral_extent(self, n: int) -> int:
        """Logical length of the forward output for an ``n``-point axis."""
        base = n // 2 + 1 if self.kind == "r2c" else n
        if self.n_keep is not None:
            if self.n_keep > base:
                raise ValueError(f"n_keep={self.n_keep} exceeds spectrum length {base} (n={n})")
            return self.n_keep
        return base

    def tag(self) -> str:
        """Stable string form (tuner cache keys, benchmark reports)."""
        if self.kind in ("dct", "dst"):
            return f"{self.kind}{self.trig_type}"
        return self.kind if self.n_keep is None else f"{self.kind}[{self.n_keep}]"


def as_spec(s) -> TransformSpec:
    """Coerce a user-facing transform description to a TransformSpec:
    accepts a TransformSpec or a tag string ("c2c", "r2c", "dct2", "dct3",
    "dst2", "dst3")."""
    if isinstance(s, TransformSpec):
        return s
    if isinstance(s, str):
        if s in ("c2c", "r2c"):
            return TransformSpec(s)
        if s in ("dct2", "dct3", "dst2", "dst3"):
            return TransformSpec(s[:3], trig_type=int(s[3]))
        raise ValueError(f"unknown transform tag {s!r}")
    raise TypeError(f"cannot interpret {s!r} as a TransformSpec")


def dealias_grid(n_keep: int) -> int:
    """Physical grid size of the 3/2-rule dealiased axis keeping ``n_keep``
    modes (the M of M = 3N/2)."""
    return (3 * n_keep) // 2


# ---------------------------------------------------------------------------
# Transform application
# ---------------------------------------------------------------------------


def local_transform(x, axis: int, sign: int, spec: TransformSpec, *, n: int,
                    impl: str = "jnp", nbatch: int = 0):
    """One stage of the plan along a locally-complete ``axis``.

    Forward (``sign == FORWARD``): input logical length ``n`` ->
    ``spec.spectral_extent(n)``.  Backward: the exact reverse.  Pruning
    (``spec.n_keep``) is folded in here — the forward keep / backward
    zero-scatter is emitted adjacent to the transform so it fuses with the
    surrounding exchange unpack instead of costing a separate HBM pass.

    ``nbatch`` leading axes of ``x`` are stacked field/batch axes and
    ``axis`` stays field-relative (the batched plan executor transforms
    all N fields of a stacked block in one vectorized call — every kernel
    here is axis-generic, so the batch rides for free).

    The work is named in the program (:mod:`repro.core.spans`): the
    transform ``xform`` (the dense c2r's ops too, under ``jit(irfft)``),
    the pruning ``prune``, the spectrum building and unpacking around a
    c2r's inverse FFT ``c2r_extend``.
    """
    axis = axis + nbatch
    if spec.kind == "c2c":
        if sign == FORWARD:
            y = _fft(x, axis, FORWARD, impl)
            if spec.n_keep is not None:
                y = _keep_centered(y, axis, spec.n_keep)
            return y
        if spec.n_keep is not None:
            x = _scatter_centered(x, axis, n, spec.n_keep)
        return _fft(x, axis, BACKWARD, impl)

    if spec.kind == "r2c":
        nbins = n // 2 + 1
        if sign == FORWARD:
            y = _rfft(x, axis, impl)
            if spec.n_keep is not None and spec.n_keep < nbins:
                with spans.kind("prune"):
                    y = lax.slice_in_dim(y, 0, spec.n_keep, axis=axis)
            return y
        return _irfft(x, axis, n, impl, nbatch)

    # dct / dst: real-to-real, forward type 2 or 3, backward its inverse
    inverse = sign == BACKWARD
    trig_type = spec.trig_type if not inverse else {2: 3, 3: 2}[spec.trig_type]
    fn = _dct_complex_safe if spec.kind == "dct" else _dst_complex_safe
    with spans.kind("xform"):
        return fn(x, axis, trig_type, impl, scale=(1.0 / (2 * n)) if inverse else 1.0)


# -- FFT vendor dispatch ----------------------------------------------------


@spans.under("xform")
def _fft(x, axis, sign, impl):
    if impl == "jnp":
        return jnp.fft.fft(x, axis=axis) if sign == FORWARD else jnp.fft.ifft(x, axis=axis)
    if impl == "matmul":
        from repro.kernels.fft import ops as fft_ops

        return fft_ops.fft_matmul(x, axis=axis, inverse=(sign == BACKWARD))
    raise ValueError(f"unknown fft impl {impl!r}")


@spans.under("xform")
def _rfft(x, axis, impl):
    if impl == "jnp":
        return jnp.fft.rfft(x, axis=axis)
    if impl == "matmul":
        from repro.kernels.fft import ops as fft_ops

        return fft_ops.rfft_matmul(x, axis=axis)
    raise ValueError(f"unknown fft impl {impl!r}")


def _irfft(x, axis, n, impl, nbatch):
    """c2r: the real inverse DFT of length ``n`` of the ``x.shape[axis]``
    leading bins of a Hermitian spectrum, the bins past them zero (a
    pruned r2c axis's zero-pad, folded in).  The imaginary parts of the DC
    and Nyquist bins drop out, as in ``numpy.fft.irfft``.

    The form is the shape's alone, whatever ``impl``: up to
    :data:`_C2R_DFT_MAX_N` points (:func:`_c2r_dense`) the dense real DFT
    from the kept bins (:func:`_c2r_dft`); past it the Hermitian extension
    and a complex inverse FFT, two real rows to one (:func:`_c2r_packed`)
    where the block has an axis to split them along (:func:`_c2r_split`),
    else one per row (:func:`_c2r_full`).

    XLA's own IRFFT is not used: on a TPU v5e it returned a (384, 384,
    193) -> n=384 c2r along the last axis at relative L2 error 0.35, where
    the extension form gives 1.3e-7."""
    if _c2r_dense(n):
        return _c2r_dft(x, axis, n, nbatch)
    split = _c2r_split(x.shape, axis, nbatch)
    if split is None:
        return _c2r_full(x, axis, n, impl)
    return _c2r_packed(x, axis, n, impl, split)


#: output length up to which the c2r is the dense real DFT.  On a TPU v5e
#: it beat the packed inverse FFT at every n measured up to here, dealiased
#: (n//3+1 kept bins) and unpruned (n//2+1), so at every m; its cost grows
#: as n·m, the FFT's as n log n, and past here nothing was measured
#: (PERF.md, the crossover table)
_C2R_DFT_MAX_N = 1024


def _c2r_dense(n):
    """Whether the c2r to ``n`` points is the dense DFT from the kept bins."""
    return n <= _C2R_DFT_MAX_N


@spans.under("xform")
def _c2r_dft(x, axis, n, nbatch=0):
    """The c2r as a dense real inverse DFT from the kept bins: one
    contraction of their real and imaginary parts side by side with
    :func:`_c2r_dft_matrix`, with no spectrum of ``n`` bins ever built."""
    return irfft(x, axis=axis, n=n, nbatch=nbatch)


#: the MXU's width: the DFT's contraction is padded to whole passes of it
_MXU_WIDTH = 128


@functools.partial(jax.jit, static_argnames=("axis", "n", "nbatch"))
def irfft(x, *, axis, n, nbatch):
    """The real inverse DFT of :func:`_c2r_dft`, its own jitted function:
    the program names its ops ``jit(irfft)``, the inverse real FFT they
    compute.  The contraction runs on the MXU at HIGHEST precision (the
    chip's default float32 dot is one bf16 pass).

    Two choices keep every output bit independent of what else the block
    holds.  The ``nbatch`` leading axes are the dot's batch axes, so a
    field's rows go through the same dot as when it is transformed alone.
    The kept bins past DC are zero-padded to whole MXU passes, so the bins
    a pruned axis drops and the zeros an unpruned spectrum holds there add
    the same exact zeros to the same contraction."""
    m = x.shape[axis]
    weights = _c2r_dft_matrix(n, m)
    pads = [(0, 0, 0)] * x.ndim
    pads[axis] = (0, weights.shape[0] // 2 - m, 0)
    zero = jnp.zeros((), jnp.float32)
    z = jnp.concatenate([lax.pad(jnp.real(x), zero, pads), lax.pad(jnp.imag(x), zero, pads)],
                        axis=axis)
    batch = tuple(range(nbatch))
    y = lax.dot_general(z, jnp.broadcast_to(weights, x.shape[:nbatch] + weights.shape),
                        (((axis,), (nbatch,)), (batch, batch)),
                        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
    # the batch axes, the other free axes of ``x`` in order, then the n points
    return jnp.moveaxis(y, -1, axis)


@functools.lru_cache(maxsize=None)
def _c2r_dft_matrix(n, m):
    """The weights of :func:`irfft` for ``m`` kept bins to ``n`` points,
    computed in float64 and rounded once: ``w_k cos(2πjk/n)/n`` in row
    ``k`` and ``-w_k sin(2πjk/n)/n`` in row ``r + k``, for the kept bins
    ``k < m`` and ``r`` rows a half (DC and whole MXU passes), the rest
    zero.  ``w_k`` is 2, and 1 for DC and for the Nyquist bin of an even
    ``n``, whose imaginary parts drop out (their sine rows are zero)."""
    rows = 1 + -(-(m - 1) // _MXU_WIDTH) * _MXU_WIDTH
    k = np.arange(rows)[:, None]
    phase = 2 * np.pi * ((k * np.arange(n)[None, :]) % n) / n
    edge = (k == 0) | (2 * k == n)
    w = np.where(k < m, np.where(edge, 1.0, 2.0) / n, 0.0)
    cos = w * np.cos(phase)
    sin = np.where(edge, 0.0, -w * np.sin(phase))
    return np.concatenate([cos, sin]).astype(np.float32)


def _c2r_split(shape, axis, nbatch):
    """The axis whose halves share the c2r's inverse FFTs: the outermost
    spatial axis of even length other than the c2r ``axis``, or None.
    The ``nbatch`` leading batch axes are never split: a packed pair
    shares rounding of the order of its larger row, so pairing a small
    field with a large one would cost the small one the digits of their
    ratio."""
    return next((a for a in range(nbatch, len(shape)) if a != axis and shape[a] % 2 == 0),
                None)


def _c2r_full(x, axis, n, impl):
    """One inverse FFT per row, of the row's own Hermitian extension: the
    form of a block with no even spatial axis to split (1-D, or every
    other spatial axis odd), where packing would need a zero partner row
    and a pad and a slice pass over the block to make one."""
    with spans.kind("c2r_extend"):
        z = _hermitian_extension(x, jnp.conj(x), axis, n)
    y = _fft(z, axis, BACKWARD, impl)
    with spans.kind("c2r_extend"):
        return jnp.real(y)


def _c2r_packed(x, axis, n, impl, split):
    """One inverse FFT per pair of rows, the halves of ``split``."""
    m = x.shape[axis]
    with spans.kind("c2r_extend"):
        half = x.shape[split] // 2
        a = lax.slice_in_dim(x, 0, half, axis=split)
        b = lax.slice_in_dim(x, half, 2 * half, axis=split)
        ar, ai, br, bi = jnp.real(a), jnp.imag(a), jnp.real(b), jnp.imag(b)
        # A + iB, without the DC and Nyquist bins' imaginary parts, which
        # would leak from A into B's output and from B into A's
        k = lax.broadcasted_iota(jnp.int32, [m if d == axis else 1 for d in range(x.ndim)],
                                 axis)
        edge = (k == 0) | (k == n // 2) if n % 2 == 0 else k == 0
        head = lax.complex(ar - jnp.where(edge, 0, bi), jnp.where(edge, 0, ai) + br)
        mirror = lax.complex(ar + bi, br - ai)  # conj(A) + i conj(B)
        z = _hermitian_extension(head, mirror, axis, n)
    y = _fft(z, axis, BACKWARD, impl)
    with spans.kind("c2r_extend"):
        return jnp.concatenate([jnp.real(y), jnp.imag(y)], axis=split)


def _hermitian_extension(head, mirror, axis, n):
    """The ``n`` bins of a c2r spectrum from its ``m`` kept bins: bins
    ``0..m-1`` from ``head``, zeros, and bin ``n-k`` from ``mirror[k]``
    for each kept ``k >= 1`` below ``n - n//2``.  With ``mirror =
    conj(head)`` this is the Hermitian extension of the kept bins."""
    m = head.shape[axis]
    t = min(m - 1, n - n // 2 - 1)
    parts = [head]
    if n - m - t:
        shape = list(head.shape)
        shape[axis] = n - m - t
        parts.append(jnp.zeros(shape, head.dtype))
    if t:
        parts.append(jnp.flip(lax.slice_in_dim(mirror, 1, 1 + t, axis=axis), axis))
    return jnp.concatenate(parts, axis=axis) if len(parts) > 1 else head


# -- pruning (truncated spectra / 3/2-rule dealiasing) ----------------------


@spans.under("prune")
def _keep_centered(y, axis, k):
    """Keep the ``k`` lowest-|frequency| modes of an fft-ordered axis:
    the first ceil(k/2) (non-negative) and last floor(k/2) (negative).

    Static slices, not an index gather: XLA on the TPU lowers a gather of
    a contiguous range to a ``while`` loop of row copies."""
    n = y.shape[axis]
    if k == n:
        return y
    head = (k + 1) // 2
    tail = k - head
    lo = lax.slice_in_dim(y, 0, head, axis=axis)
    if tail == 0:
        return lo
    hi = lax.slice_in_dim(y, n - tail, n, axis=axis)
    return jnp.concatenate([lo, hi], axis=axis)


@spans.under("prune")
def _scatter_centered(y, axis, n, k):
    """Inverse of :func:`_keep_centered`: zero-pad the retained modes back
    into an ``n``-long fft-ordered axis (head, ``n - k`` zeros, tail)."""
    if k == n:
        return y
    head = (k + 1) // 2
    tail = k - head
    lo = lax.slice_in_dim(y, 0, head, axis=axis)
    mid_shape = list(y.shape)
    mid_shape[axis] = n - k
    mid = jnp.zeros(mid_shape, y.dtype)
    if tail == 0:
        return jnp.concatenate([lo, mid], axis=axis)
    hi = lax.slice_in_dim(y, head, k, axis=axis)
    return jnp.concatenate([lo, mid, hi], axis=axis)


# -- DCT / DST via the FFT-based even/odd extension trick -------------------


def _dct_complex_safe(x, axis, trig_type, impl, scale=1.0):
    if jnp.iscomplexobj(x):
        return (_dct_real(jnp.real(x), axis, trig_type, impl)
                + 1j * _dct_real(jnp.imag(x), axis, trig_type, impl)) * scale
    y = _dct_real(x, axis, trig_type, impl)
    return y * scale if scale != 1.0 else y


def _dst_complex_safe(x, axis, trig_type, impl, scale=1.0):
    """DST-II/III via the DCT: DST-II(x) = reverse(DCT-II((-1)^j x)),
    DST-III(x) = (-1)^k DCT-III(reverse(x)).  The matmul impl skips the
    reduction and applies the sine matrix in one shot."""
    if impl == "matmul":
        from repro.kernels.fft import ops as fft_ops

        y = fft_ops.dst_matmul(x, axis=axis, trig_type=trig_type)
        return y * scale if scale != 1.0 else y
    n = x.shape[axis]
    sgn = _alternating(n, x.ndim, axis)
    if trig_type == 2:
        y = _dct_complex_safe(x * sgn, axis, 2, impl, scale=scale)
        return jnp.flip(y, axis=axis)
    y = _dct_complex_safe(jnp.flip(x, axis=axis), axis, 3, impl, scale=scale)
    return y * sgn


def _alternating(n, ndim, axis):
    s = (-1.0) ** jnp.arange(n, dtype=jnp.float32)
    return s.reshape([n if i == axis % ndim else 1 for i in range(ndim)])


def _dct_real(x, axis, trig_type, impl):
    """Unnormalized (scipy-convention) DCT-II or DCT-III of a real block."""
    if impl == "matmul":
        from repro.kernels.fft import ops as fft_ops

        return fft_ops.dct_matmul(x, axis=axis, trig_type=trig_type)
    n = x.shape[axis]
    xl = jnp.moveaxis(x, axis, -1)
    if trig_type == 2:
        # Makhoul: permute to v = [x0, x2, ..., x5, x3, x1], one length-n FFT
        v = jnp.concatenate([xl[..., ::2], xl[..., 1::2][..., ::-1]], axis=-1)
        vf = jnp.fft.fft(v, axis=-1)
        k = jnp.arange(n)
        y = jnp.real(2 * jnp.exp(-1j * jnp.pi * k / (2 * n)) * vf)
    else:
        # DCT-III = 2n x the inverse of DCT-II (verified vs scipy)
        k = jnp.arange(n)
        xr = jnp.concatenate([jnp.zeros_like(xl[..., :1]), xl[..., :0:-1]], axis=-1)
        vf = 0.5 * jnp.exp(1j * jnp.pi * k / (2 * n)) * (xl - 1j * xr)
        v = jnp.real(jnp.fft.ifft(vf, axis=-1)) * (2 * n)
        h = (n + 1) // 2
        y = jnp.zeros_like(xl)
        y = y.at[..., ::2].set(v[..., :h])
        y = y.at[..., 1::2].set(v[..., h:][..., ::-1])
    return jnp.moveaxis(y.astype(x.dtype), -1, axis)

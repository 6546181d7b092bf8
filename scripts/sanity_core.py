"""Quick sanity: exchange + ParallelFFT on 8 virtual host devices."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.meshutil import make_mesh
from repro.core.pencil import make_pencil, pad_global, unpad_global
from repro.core.redistribute import exchange
from repro.core.pfft import ParallelFFT

mesh = make_mesh((2, 4), ("p0", "p1"))
print("mesh", mesh)

# --- exchange correctness: fused vs traditional vs numpy oracle ---
rng = np.random.default_rng(0)
shape = (8, 12, 16)
x = rng.standard_normal(shape).astype(np.float32)

src = make_pencil(mesh, shape, ("p0", "p1", None), divisors=(4, 2, 1))
xp = pad_global(jnp.asarray(x), src)
xs = jax.device_put(xp, src.sharding)

for method in ("fused", "traditional", "pipelined"):
    y, dst = exchange(xs, src, v=2, w=1, method=method, chunks=2)
    # oracle: exchange just realigns; global array unchanged
    got = unpad_global(np.asarray(y), dst)
    np.testing.assert_allclose(got, x, rtol=1e-6)
    print(f"exchange[{method}] ok; dst placement={dst.placement}")

# --- ParallelFFT: pencil 2D grid c2c ---
for real in (False, True):
    for gridspec in (("p0",), ("p0", "p1"), (("p0", "p1"),)):
        transforms = ("c2c", "c2c", "r2c") if real else None
        plan = ParallelFFT(mesh, (16, 12, 20), gridspec, transforms=transforms)
        xin = rng.standard_normal((16, 12, 20)).astype(np.float32)
        if not real:
            xin = (xin + 1j * rng.standard_normal((16, 12, 20))).astype(np.complex64)
        xg = jax.device_put(pad_global(jnp.asarray(xin), plan.input_pencil), plan.input_pencil.sharding)
        yhat = plan.forward(jnp.asarray(xin))
        want = np.fft.rfftn(xin) if real else np.fft.fftn(xin)
        np.testing.assert_allclose(np.asarray(yhat), want / 1.0, rtol=2e-4, atol=2e-3)
        back = plan.backward(yhat)
        np.testing.assert_allclose(np.asarray(back), xin, rtol=2e-4, atol=2e-3)
        print(f"pfft real={real} grid={gridspec} ok")

# 4D on 3D grid
mesh3 = make_mesh((2, 2, 2), ("a", "b", "c"))
plan = ParallelFFT(mesh3, (8, 8, 8, 8), ("a", "b", "c"))
xin = (rng.standard_normal((8, 8, 8, 8)) + 1j * rng.standard_normal((8, 8, 8, 8))).astype(np.complex64)
yhat = plan.forward(jnp.asarray(xin))
np.testing.assert_allclose(np.asarray(yhat), np.fft.fftn(xin), rtol=2e-4, atol=2e-3)
print("pfft 4D/3Dgrid ok")

# kernels
from repro.kernels.fft import ops as fops
x1 = (rng.standard_normal((4, 96)) + 1j * rng.standard_normal((4, 96))).astype(np.complex64)
np.testing.assert_allclose(np.asarray(fops.fft_matmul(jnp.asarray(x1))), np.fft.fft(x1, axis=-1), rtol=2e-4, atol=2e-3)
x2 = rng.standard_normal((4, 384)).astype(np.float32)
np.testing.assert_allclose(np.asarray(fops.rfft_matmul(jnp.asarray(x2))), np.fft.rfft(x2, axis=-1), rtol=2e-4, atol=2e-2)
from repro.core.fftcore import BACKWARD, TransformSpec, local_transform
back = local_transform(jnp.asarray(np.fft.rfft(x2)), 1, BACKWARD, TransformSpec.r2c(), n=384, impl="matmul")
np.testing.assert_allclose(np.asarray(back), x2, rtol=2e-4, atol=2e-3)
print("fft kernels ok")

from repro.kernels.transpose.ops import transpose01
x3 = rng.standard_normal((6, 10, 5)).astype(np.float32)
np.testing.assert_allclose(np.asarray(transpose01(jnp.asarray(x3))), x3.swapaxes(0, 1))
print("transpose kernel ok")
print("ALL SANITY OK")

"""Step kind ``dns_rk2``: one RK2 step of a dealiased pseudo-spectral DNS,
written as a user of the library writes it (after
``examples/navier_stokes.py``).

Each right-hand side makes one 3-field backward (the velocity), one
9-field backward (the velocity gradients) and one 3-field forward (the
convective term) batched call of a ``pruned(N), pruned(N), r2c(N/2+1)``
plan on the padded ``M = 3N/2`` grid; a step evaluates two.  The whole
step is one jitted, ahead-of-time compiled executable; the window chains
it on the state it returned.

The compared answer is one step drawn from the seed: its input and output
state are read back once the window has closed, and the increment the
program made is compared with the configuration's float64 reference of
the same step.
"""

from __future__ import annotations

import numpy as np

from bench import common
from bench.workcount import field_transform

#: field-transforms of one step: per right-hand side 3 + 9 backward and
#: 3 forward, two right-hand sides
TRANSFORMS_PER_STEP = 2 * (3 + 9 + 3)


def _specs(cfg):
    from repro.core.fftcore import TransformSpec

    return tuple(TransformSpec(t["kind"], n_keep=t.get("keep")) for t in cfg["transforms"])


def _wavenumbers(n):
    import jax.numpy as jnp

    k = jnp.fft.fftfreq(n, 1.0 / n).astype(jnp.float32)
    kz = jnp.arange(n // 2 + 1, dtype=jnp.float32)
    return k[:, None, None], k[None, :, None], kz[None, None, :]


def make_step(plan, n: int, m: int, nu: float, dt: float):
    """The user's RK2 step on the retained state ``(3, N, N, N//2+1)``."""
    import jax.numpy as jnp

    scale = float(m) ** 3

    def fwd(u):
        return plan.forward(u) / scale

    def bwd(c):
        return plan.backward(c * scale)

    def project(v, kx, ky, kz):
        k2 = kx**2 + ky**2 + kz**2
        k2 = jnp.where(k2 == 0, 1.0, k2)
        div = (kx * v[0] + ky * v[1] + kz * v[2]) / k2
        return jnp.stack([v[0] - kx * div, v[1] - ky * div, v[2] - kz * div])

    def rhs(u_hat):
        kx, ky, kz = _wavenumbers(n)
        herm = ((kx != -n // 2) & (ky != -n // 2)).astype(jnp.float32)
        u = bwd(u_hat)                                         # (3, M, M, M)
        ik_u = jnp.stack([1j * k * u_hat[i] for i in range(3) for k in (kx, ky, kz)])
        grads = bwd(ik_u).reshape(3, 3, m, m, m)               # d_j u_i
        # (u . grad) u as an elementwise sum: a float32 einsum would be a
        # dot at the chip's default (bf16-pass) precision
        conv = sum(u[j] * grads[:, j] for j in range(3))
        conv_hat = fwd(conv) * herm
        return project(-conv_hat, kx, ky, kz) - nu * (kx**2 + ky**2 + kz**2) * u_hat

    def step(u_hat):
        k1 = rhs(u_hat)
        k2 = rhs(u_hat + dt * k1)
        return project(u_hat + 0.5 * dt * (k1 + k2), *_wavenumbers(n))

    return step


def make_initial(n: int, init: dict):
    """Taylor-Green vortex ``u = sin x cos y cos z, v = -cos x sin y cos z``
    in retained coefficients, plus a solenoidal, Hermitian-consistent
    perturbation with a Gaussian envelope, drawn from the key."""
    import jax
    import jax.numpy as jnp

    amp, k0 = init["perturbation"], init["perturbation_k0"]

    def initial(key):
        kx, ky, kz = _wavenumbers(n)
        shape = (3, n, n, n // 2 + 1)
        tg = (jnp.abs(kx) == 1) & (jnp.abs(ky) == 1) & (kz == 1)
        base = jnp.stack([jnp.where(tg, -1j * jnp.sign(kx) / 8, 0),
                          jnp.where(tg, 1j * jnp.sign(ky) / 8, 0),
                          jnp.zeros(shape[1:], jnp.complex64)]).astype(jnp.complex64)
        kr, ki = jax.random.split(key)
        env = jnp.exp(-(kx**2 + ky**2 + kz**2) / k0**2)
        p = jax.lax.complex(jax.random.normal(kr, shape), jax.random.normal(ki, shape)) * env
        # the kz = 0 plane of a real field is Hermitian: c(-k) = conj(c(k))
        plane = p[..., 0]
        mirror = jnp.roll(jnp.flip(plane, (1, 2)), 1, axis=(1, 2))
        p = p.at[..., 0].set(0.5 * (plane + jnp.conj(mirror)))
        p = p * (amp / jnp.sqrt(jnp.sum(jnp.abs(p) ** 2) / 3))
        v = base + p
        k2 = kx**2 + ky**2 + kz**2
        div = (kx * v[0] + ky * v[1] + kz * v[2]) / jnp.where(k2 == 0, 1.0, k2)
        v = jnp.stack([v[0] - kx * div, v[1] - ky * div, v[2] - kz * div])
        herm = (kx != -n // 2) & (ky != -n // 2)
        return jnp.where(herm, v, 0).astype(jnp.complex64)

    return initial


class Cell:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        from repro.core.pfft import ParallelFFT
        from repro.core.planconfig import PlanConfig

        cfg, self.ctx = ctx.config, ctx
        self.n, self.m = n, m = cfg["modes"], cfg["points"]
        self.nu, self.dt = cfg["nu"], cfg["dt"]
        self.min_steps = ctx.traffic["min_steps"]
        shape = (3, n, n, n // 2 + 1)
        with ctx.span("plan.compile"):
            self.plan = ParallelFFT(
                ctx.mesh, (m, m, m), ctx.grid, transforms=_specs(cfg),
                config=PlanConfig(**{**cfg["plan_config"], **ctx.plan_overrides}))
            sharding = self.plan.output_pencil.batched_sharding(1)
            spec = jax.ShapeDtypeStruct(shape, jnp.complex64, sharding=sharding)
            step = make_step(self.plan, n, m, self.nu, self.dt)
            self.step = jax.jit(step).lower(spec).compile()
            self.initial = jax.jit(make_initial(n, cfg["initial"]), out_shardings=sharding)
        self.work = TRANSFORMS_PER_STEP * field_transform((m, m, m), cfg["transforms"])
        self.executables = {"step": self.step}

    def reset(self, seed: int):
        import jax

        self.u = jax.block_until_ready(self.initial(jax.random.key(seed)))
        self.sample = common.sample_index(seed, self.min_steps)
        self.kept = None

    def dispatch(self, i: int):
        u_in = self.u
        self.u = self.step(u_in)
        if i == self.sample:
            self.kept = (u_in, self.u)
        return self.u

    def check(self) -> list[common.Check]:
        u_in, u_out = (np.asarray(a) for a in self.kept)
        self.u = self.kept = None
        ref = self.ctx.reference.rk2_step(u_in, self.n, self.m, self.nu, self.dt)
        u_in = u_in.astype(np.complex128)
        du_ref = ref - u_in
        du = u_out.astype(np.complex128) - u_in
        rel_l2, worst = common.gaps(du, du_ref)
        limits = self.ctx.config["checks"]
        return [common.Check("du_rel_l2", rel_l2, limits["du_rel_l2"]),
                common.Check("du_worst", worst, limits["du_worst"])]


def build(ctx) -> Cell:
    return Cell(ctx)

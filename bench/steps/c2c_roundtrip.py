"""Step kind ``c2c_roundtrip``: a forward then backward transform of one
field through a ``ParallelFFT`` plan, each an ahead-of-time compiled
``jax.jit`` of the plan's entry point, as a spectral code that goes to
spectral space and back does.  Every step transforms the same field, made
on the device from the seed.

The compared answers are the forward spectrum and the round trip of two
steps, one drawn from the seed and the window's last, read back once the
window has closed: the spectrum against the configuration's float64
reference, the round trip against the input.
"""

from __future__ import annotations

import numpy as np

from bench import common
from bench.workcount import field_transform


class Cell:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        from repro.core.pfft import ParallelFFT
        from repro.core.planconfig import PlanConfig

        cfg, self.ctx = ctx.config, ctx
        self.shape = tuple(cfg["shape"])
        self.min_steps = ctx.traffic["min_steps"]
        with ctx.span("plan.compile"):
            plan = ParallelFFT(
                ctx.mesh, self.shape, ctx.grid,
                config=PlanConfig(**{**cfg["plan_config"], **ctx.plan_overrides}))
            self.sharding = plan.input_pencil.sharding
            xspec = jax.ShapeDtypeStruct(self.shape, jnp.complex64, sharding=self.sharding)
            self.fwd = jax.jit(plan.forward).lower(xspec).compile()
            yspec = jax.ShapeDtypeStruct(plan.output_pencil.logical, plan.spectral_dtype,
                                         sharding=self.fwd.output_shardings)
            self.bwd = jax.jit(plan.backward).lower(yspec).compile()
        self.work = 2 * field_transform(self.shape, cfg["transforms"])
        self.executables = {"forward": self.fwd, "backward": self.bwd}

    def reset(self, seed: int):
        import jax

        self.x = jax.block_until_ready(
            common.random_field(seed, self.shape, np.complex64, self.sharding))
        self.sample = common.sample_index(seed, self.min_steps)
        self.kept = {}

    def dispatch(self, i: int):
        y = self.fwd(self.x)
        back = self.bwd(y)
        if i == self.sample:
            self.kept["sample"] = (y, back)
        self.kept["last"] = (y, back)
        return back

    def check(self) -> list[common.Check]:
        x = np.asarray(self.x)
        answers = [tuple(np.asarray(a) for a in pair) for pair in self.kept.values()]
        self.x = self.kept = None
        if len(answers) == 2 and all(np.array_equal(a, b) for a, b in zip(*answers)):
            answers = answers[:1]  # the same bits compare the same
        ref = self.ctx.reference.forward(x)
        x = x.astype(np.complex128)
        limits = self.ctx.config["checks"]
        measured = []
        for y, back in answers:
            fwd, rt = common.gaps(y, ref), common.gaps(back, x)
            measured.append({"fwd_rel_l2": fwd[0], "fwd_worst": fwd[1],
                             "rt_rel_l2": rt[0], "rt_worst": rt[1]})
        # np.max keeps a NaN
        return [common.Check(name, float(np.max([m[name] for m in measured])), limits[name])
                for name in limits]


def build(ctx) -> Cell:
    return Cell(ctx)

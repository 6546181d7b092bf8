"""Scaling-proof harness: weak/strong sweeps with analytic-model fits.

The paper's entire evaluation (Sec. 5, Figs. 6-11) is strong/weak scaling;
this driver is our machine-checked version of it.  It sweeps

    grid size x device count x fields x (slab | pencil)

in subprocesses (one fresh python per point with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — the same device
scaling :mod:`benchmarks.paperfigs` uses, and the same inner/outer
best-of-N methodology as :mod:`benchmarks.fftbench`, which is the worker).
Each point carries the measured time *and* the analytic model terms
(:meth:`ParallelFFT.model_time_s` decomposed into the linear surrogate of
:mod:`repro.core.modelfit`), so after the sweep the harness

* least-squares fits the bandwidth/latency coefficients per series,
* flags >2x model misses into a machine-readable residual report
  (``modelfit_report.json`` — arm it as tuner priors via
  ``REPRO_MODEL_PRIORS`` to prune future candidate sweeps),
* normalizes everything into one ``bench-v3`` record
  (:func:`benchmarks.normalize_bench.normalize_scaling`) — the input of
  the ``benchmarks/benchdiff.py`` regression gate in CI,
* and (``--figures``) renders paper-style weak/strong scaling and
  redistribution-split figures via :mod:`benchmarks.paperfigs`.

Presets:

``smoke``   — the CI PR-gate sweep: tiny shapes, ndev in {1,2,4}/{2,4,8},
              strong+weak on slab and pencil, one 3-field series, a
              redistribution split on the strong 16^3 series.  This is
              also what produces the committed ``BENCH_prN.json`` records.
``nightly`` — larger shapes up to 8 devices, an ``auto`` tuned series and
              a bf16-payload series on top of the smoke matrix.

Usage:
    python -m benchmarks.scalebench --preset smoke --out benchmarks/artifacts/scaling
    python -m benchmarks.scalebench --preset nightly --figures --pr 10
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
DEFAULT_OUT = REPO / "benchmarks" / "artifacts" / "scaling"


def _series_name(s: dict) -> str:
    """Stable series key: mode@grid@shape@method@dtype@impl[@fN] — the
    ``method@dtype@impl`` triple is what benchdiff matches records on."""
    shape_tag = "x".join(map(str, s["shape"]))
    if s["mode"] == "weak":
        shape_tag = "loc" + shape_tag  # per-device local shape
    name = (f"{s['mode']}@{s['grid']}@{shape_tag}"
            f"@{s['method']}@{s.get('comm_dtype') or 'complex64'}"
            f"@{s.get('exchange_impl', 'jnp')}")
    if s.get("fields", 1) > 1:
        name += f"@f{s['fields']}"
    return name


def _point_shape(s: dict, ndev: int) -> tuple[int, ...]:
    """Strong scaling holds the global shape; weak scaling scales the
    leading axis with the device count (paper Figs. 8-9: fixed per-core
    local size)."""
    shape = tuple(s["shape"])
    if s["mode"] == "weak":
        return (shape[0] * ndev, *shape[1:])
    return shape


def preset_series(preset: str) -> list[dict]:
    slab_devs, pencil_devs = (1, 2, 4), (2, 4, 8)
    if preset == "smoke":
        base, big = (16, 16, 16), (32, 16, 16)
        weak_local = (8, 16, 16)
        series = []
        for grid, devs in (("slab", slab_devs), ("pencil", pencil_devs)):
            for method in ("fused", "traditional"):
                series.append({"mode": "strong", "grid": grid, "shape": base,
                               "method": method, "devices": devs, "split": True})
                series.append({"mode": "strong", "grid": grid, "shape": big,
                               "method": method, "devices": devs})
            series.append({"mode": "weak", "grid": grid, "shape": weak_local,
                           "method": "fused", "devices": devs})
        series.append({"mode": "strong", "grid": "slab", "shape": base,
                       "method": "fused", "devices": slab_devs, "fields": 3})
        return series
    if preset == "nightly":
        slab_devs, pencil_devs = (1, 2, 4, 8), (2, 4, 8)
        base, big = (32, 32, 32), (64, 32, 32)
        weak_local = (16, 32, 32)
        series = []
        for grid, devs in (("slab", slab_devs), ("pencil", pencil_devs)):
            for method in ("fused", "traditional"):
                series.append({"mode": "strong", "grid": grid, "shape": base,
                               "method": method, "devices": devs, "split": True})
                series.append({"mode": "strong", "grid": grid, "shape": big,
                               "method": method, "devices": devs})
            series.append({"mode": "weak", "grid": grid, "shape": weak_local,
                           "method": "fused", "devices": devs, "split": True})
            # tuned schedules and the lossy-wire trade at scale
            series.append({"mode": "strong", "grid": grid, "shape": base,
                           "method": "auto", "devices": devs, "tune": True})
            series.append({"mode": "strong", "grid": grid, "shape": base,
                           "method": "fused", "comm_dtype": "bf16",
                           "devices": devs})
        series.append({"mode": "strong", "grid": "slab", "shape": base,
                       "method": "fused", "devices": slab_devs, "fields": 3})
        series.append({"mode": "strong", "grid": "pencil", "shape": base,
                       "method": "fused", "devices": pencil_devs, "fields": 3})
        return series
    raise SystemExit(f"unknown preset {preset!r} (smoke | nightly)")


def run_point(shape, ndev: int, *, grid: str, method: str, measure: str,
              fields: int = 1, comm_dtype: str | None = None,
              exchange_impl: str = "jnp", inner: int, outer: int,
              tune_cache: str | None = None, timeout: int = 1800) -> dict:
    """One fftbench worker subprocess at ``ndev`` virtual host devices."""
    env = dict(os.environ)
    # CPU-only sweep: the child never competes for an accelerator, and
    # its record honestly says "cpu"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = (str(REPO / "src") + os.pathsep + str(REPO)
                         + (os.pathsep + env["PYTHONPATH"]
                            if env.get("PYTHONPATH") else ""))
    cmd = [sys.executable, "-m", "benchmarks.fftbench",
           "--shape", ",".join(map(str, shape)), "--grid", grid,
           "--method", method, "--measure", measure,
           "--inner", str(inner), "--outer", str(outer)]
    if fields > 1:
        cmd += ["--fields", str(fields)]
    if comm_dtype:
        cmd += ["--comm-dtype", comm_dtype]
    if exchange_impl != "jnp":
        cmd += ["--exchange-impl", exchange_impl]
    if tune_cache:
        cmd += ["--tune-cache", tune_cache]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"scalebench point failed: {' '.join(cmd)}\n"
                           f"{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def run_sweep(series_list: list[dict], *, inner: int, outer: int,
              tune_cache: str | None = None, log=print) -> dict:
    """Execute every series point; returns the raw sweep blob
    ``normalize_bench.normalize_scaling`` consumes."""
    t_start = time.time()
    out_series = []
    total_pts = sum(len(s["devices"]) * (2 if s.get("split") else 1)
                    - (1 if s.get("split") and 1 in s["devices"] else 0)
                    for s in series_list)
    done = 0
    for s in series_list:
        name = _series_name(s)
        points, redist_points = [], []
        for ndev in s["devices"]:
            shape = _point_shape(s, ndev)
            measures = ["total"]
            # redistribution split: exchanges-only timing (the paper's
            # "global redistribution" decomposition); meaningless on one
            # device, where no exchange exists
            if s.get("split") and ndev > 1:
                measures.append("redistribution")
            for measure in measures:
                r = run_point(shape, ndev, grid=s["grid"], method=s["method"],
                              measure=measure, fields=s.get("fields", 1),
                              comm_dtype=s.get("comm_dtype"),
                              exchange_impl=s.get("exchange_impl", "jnp"),
                              inner=inner, outer=outer,
                              tune_cache=tune_cache if s.get("tune") else None)
                done += 1
                (points if measure == "total" else redist_points).append(r)
                log(f"[{done}/{total_pts}] {name} ndev={ndev} {measure}: "
                    f"{r['best_s']:.5f}s (model {r['model']['time_s']:.2e}s)",
                    flush=True)
        entry = {"name": name, "points": points,
                 **{k: s.get(k) for k in ("mode", "grid", "method",
                                          "comm_dtype", "exchange_impl")},
                 "fields": s.get("fields", 1),
                 "base_shape": list(s["shape"])}
        if redist_points:
            entry["redist_points"] = redist_points
        out_series.append(entry)
    return {"scalebench": True, "series": out_series,
            "elapsed_s": time.time() - t_start,
            "inner": inner, "outer": outer}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--preset", default="smoke", help="smoke | nightly")
    ap.add_argument("--out", type=Path, default=DEFAULT_OUT,
                    help="artifact directory (raw sweep, BENCH record, "
                         "fit report, figures)")
    ap.add_argument("--pr", type=int, default=None,
                    help="PR number stamped on the BENCH record")
    ap.add_argument("--inner", type=int, default=2)
    ap.add_argument("--outer", type=int, default=5)
    ap.add_argument("--tune-cache", default=None,
                    help="schedule-cache path for tuned (method=auto) series")
    ap.add_argument("--figures", action="store_true",
                    help="render scaling/redistribution figures (matplotlib)")
    ap.add_argument("--update-priors", type=Path, default=None,
                    help="also write the fitted coefficients to this path "
                         "(arm with REPRO_MODEL_PRIORS for tuner priors)")
    args = ap.parse_args(argv)

    from benchmarks.normalize_bench import normalize_scaling

    args.out.mkdir(parents=True, exist_ok=True)
    raw = run_sweep(preset_series(args.preset), inner=args.inner,
                    outer=args.outer, tune_cache=args.tune_cache)
    raw["preset"] = args.preset
    (args.out / "scalebench_raw.json").write_text(json.dumps(raw, indent=1))

    bench = normalize_scaling(raw, pr=args.pr)
    report = bench.pop("_fit_report")  # full per-point residual report
    bench_path = args.out / "BENCH.json"
    bench_path.write_text(json.dumps(bench, indent=1, sort_keys=True) + "\n")
    report_path = args.out / "modelfit_report.json"
    report_path.write_text(json.dumps(report, indent=1, sort_keys=True) + "\n")
    if args.update_priors:
        from repro.core import modelfit

        modelfit.save_priors(report, args.update_priors)
        print(f"priors -> {args.update_priors} "
              f"(arm with REPRO_MODEL_PRIORS={args.update_priors})")

    pri = report["priors"]
    print(f"fit: ici_bw={pri['ici_bw']:.3e} B/s, "
          f"ici_latency={pri['ici_latency_s']:.3e} s, "
          f"{report['n_misses']} model miss(es)")
    print(f"BENCH -> {bench_path}\nreport -> {report_path}")

    if args.figures:
        from benchmarks.paperfigs import render_scaling_figures

        figs = render_scaling_figures(bench, args.out / "figs")
        print("figures ->", ", ".join(str(f) for f in figs))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

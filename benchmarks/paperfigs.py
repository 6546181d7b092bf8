"""One benchmark per paper table/figure (Figs. 6-11), container-scaled.

The paper measures wall-time on a Cray XC40 up to 4096 cores; this container
has one CPU core exposing N virtual XLA host devices.  What IS meaningful
here and what we report:

* fused-vs-traditional *relative* cost at fixed device count (the paper's
  core claim) — the traditional path pays a real, measurable local
  transpose on every exchange;
* scaling *structure* (communication volume per device, redistribution
  count) via the analytic model attached to every point;
* absolute wall-times are single-core multi-threaded and are labelled as
  such (they must NOT be read as distributed scaling).

Figs 10-11 at production scale are dry-run/roofline artifacts, produced by
``benchmarks.fft_roofline`` on the 16x16 (and 2x16x16) mesh.

Output: CSV rows ``fig,series,ndev,time_s,...`` to stdout and
``benchmarks/artifacts/figs/*.json``.

:func:`render_scaling_figures` (used by ``benchmarks.scalebench
--figures``) renders a bench-v3 record into the paper-style figures:
log-log time-vs-devices strong/weak scaling charts (measured solid,
fitted model dashed, ideal-scaling guide) and a redistribution-vs-compute
split bar chart, saved as SVG+PNG.  Needs matplotlib, which the CI
container ships but requirements.txt deliberately omits — the import is
guarded so the core package never depends on it.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
ART = REPO / "benchmarks" / "artifacts" / "figs"

SCALE = os.environ.get("REPRO_BENCH_SCALE", "small")
# container-scaled stand-ins for the paper's global sizes
SIZES = {
    "small": {
        "fig6_shape": (72, 72, 72),       # paper: 700^3 slab strong
        "fig7_shape": (64, 64, 64),       # paper: 512^3 pencil strong
        "weak_local": (32, 32, 32),       # paper: 64^2*128 per core
        "fig11_shape": (16, 16, 16, 16),  # paper: 128^4, 3-D grid
        "devs": (1, 2, 4, 8),
        "outer": 5,
    },
    "paper": {
        "fig6_shape": (700, 700, 700),
        "fig7_shape": (512, 512, 512),
        "weak_local": (64, 64, 128),
        "fig11_shape": (128, 128, 128, 128),
        "devs": (1, 2, 4, 8, 16, 32),
        "outer": 50,
    },
}[SCALE]


def run_point(shape, grid, method, ndev, *, real=True, measure="total",
              outer=None, inner=3):
    env = dict(os.environ)
    # CPU-only sweep: the child never competes for an accelerator, and
    # its record honestly says "cpu"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + str(REPO)
    cmd = [sys.executable, "-m", "benchmarks.fftbench",
           "--shape", ",".join(map(str, shape)), "--grid", grid,
           "--method", method, "--measure", measure,
           "--inner", str(inner), "--outer", str(outer or SIZES["outer"])]
    if real:
        cmd.append("--real")
    out = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"bench point failed: {cmd}\n{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _sweep(fig, shape_fn, grid, devs, *, methods=("fused", "traditional"),
           measures=("total", "redistribution")):
    rows = []
    for ndev in devs:
        for method in methods:
            for measure in measures:
                if ndev == 1 and measure == "redistribution":
                    continue
                r = run_point(shape_fn(ndev), grid, method, ndev, measure=measure)
                r["fig"] = fig
                rows.append(r)
                print(f"{fig},{method},{measure},ndev={ndev},"
                      f"shape={r['shape']},t={r['best_s']:.4f}s", flush=True)
    return rows


def fig6_slab_strong():
    shape = SIZES["fig6_shape"]
    return _sweep("fig6", lambda n: shape, "slab", SIZES["devs"])


def fig7_pencil_strong():
    shape = SIZES["fig7_shape"]
    devs = [d for d in SIZES["devs"] if d >= 2]
    return _sweep("fig7", lambda n: shape, "pencil", devs)


def fig8_slab_weak():
    lx, ly, lz = SIZES["weak_local"]
    return _sweep("fig8", lambda n: (lx * n, ly, lz), "slab", SIZES["devs"])


def fig9_pencil_weak():
    lx, ly, lz = SIZES["weak_local"]
    devs = [d for d in SIZES["devs"] if d >= 2]
    return _sweep("fig9", lambda n: (lx * n, ly, lz), "pencil", devs)


def fig11_fft4d():
    shape = SIZES["fig11_shape"]
    devs = [d for d in SIZES["devs"] if d >= 8]
    return _sweep("fig11", lambda n: shape, "grid3", devs or [8],
                  measures=("total",))


ALL = {
    "fig6": fig6_slab_strong,
    "fig7": fig7_pencil_strong,
    "fig8": fig8_slab_weak,
    "fig9": fig9_pencil_weak,
    "fig11": fig11_fft4d,
}


# ---------------------------------------------------------------------------
# bench-v3 figure rendering (scalebench --figures)
#
# Categorical palette in fixed slot order (validated set: adjacent-pair
# CVD dE >= 8 and normal-vision dE >= 15 on the light surface); chart
# chrome stays in the neutral ink/grid tokens so text never wears a
# series color.
_PALETTE = ["#2a78d6", "#eb6834", "#1baf7a", "#eda100",
            "#e87ba4", "#008300", "#4a3aa7", "#e34948"]
_SURFACE, _INK, _INK2 = "#fcfcfb", "#0b0b0b", "#52514e"
_MUTED, _GRIDLINE, _AXISLINE = "#898781", "#e1e0d9", "#c3c2b7"


def _mpl():
    try:
        import matplotlib
    except ImportError as e:  # requirements.txt omits matplotlib on purpose
        raise ImportError(
            "render_scaling_figures needs matplotlib (present in the CI "
            "image, intentionally not in requirements.txt); install it or "
            "drop --figures") from e
    matplotlib.use("Agg")
    import matplotlib.pyplot as plt
    return plt


def _style_axes(ax):
    ax.set_facecolor(_SURFACE)
    for side in ("top", "right"):
        ax.spines[side].set_visible(False)
    for side in ("left", "bottom"):
        ax.spines[side].set_color(_AXISLINE)
    ax.grid(True, which="major", color=_GRIDLINE, linewidth=0.8)
    ax.set_axisbelow(True)
    ax.tick_params(colors=_MUTED, labelsize=8, labelcolor=_INK2)


def _series_label(s: dict) -> str:
    shape = "x".join(map(str, s.get("base_shape") or ()))
    if s.get("mode") == "weak":
        shape += "/dev"
    label = f"{s.get('method')} {shape}"
    if (s.get("comm_dtype") or "complex64") != "complex64":
        label += f" {s['comm_dtype']}"
    if (s.get("exchange_impl") or "jnp") != "jnp":
        label += f" {s['exchange_impl']}"
    if (s.get("fields") or 1) > 1:
        label += f" {s['fields']}-field"
    return label


def _tint(hex_color: str, frac: float = 0.72) -> tuple:
    """Lighter step of the same hue (mix toward the surface) for the
    compute segment of the split bars — tone-on-tone, not a new hue."""
    r, g, b = (int(hex_color[i:i + 2], 16) / 255 for i in (1, 3, 5))
    return tuple(c + (1.0 - c) * frac for c in (r, g, b))


def _save(fig, outdir: Path, stem: str) -> list[Path]:
    paths = []
    for ext in ("svg", "png"):
        p = outdir / f"{stem}.{ext}"
        fig.savefig(p, dpi=160, facecolor=_SURFACE, bbox_inches="tight")
        paths.append(p)
    return paths


def _scaling_figure(plt, mode: str, grid: str, items: list) -> "object":
    from matplotlib.lines import Line2D

    fig, ax = plt.subplots(figsize=(6.4, 4.2))
    _style_axes(ax)
    ndevs = sorted({p["ndev"] for _, s in items for p in s["points"]})
    anchor = None  # (ndev, time) anchoring the ideal-scaling guide
    for slot, (_, s) in enumerate(items):
        color = _PALETTE[slot]
        pts = sorted(s["points"], key=lambda p: p["ndev"])
        xs = [p["ndev"] for p in pts]
        ys = [p["best_s"] for p in pts]
        ax.plot(xs, ys, color=color, marker="o", markersize=6,
                linewidth=2, label=_series_label(s))
        if anchor is None:
            anchor = (xs[0], ys[0])
        fit = [p.get("fit_time_s") for p in pts]
        if all(f is not None for f in fit):
            ax.plot(xs, fit, color=color, linewidth=1.4,
                    linestyle="--", alpha=0.9)
    if anchor:
        n0, t0 = anchor
        # strong scaling: ideal is t0 * n0/n; weak: flat per-device time
        ideal = [t0 * n0 / n if mode == "strong" else t0 for n in ndevs]
        ax.plot(ndevs, ideal, color=_MUTED, linewidth=1.2, linestyle=":")
    ax.set_xscale("log", base=2)
    ax.set_yscale("log")
    ax.set_xticks(ndevs, [str(n) for n in ndevs])
    ax.minorticks_off()
    ax.set_xlabel("devices", color=_INK2, fontsize=9)
    ax.set_ylabel("wall time per transform (s)", color=_INK2, fontsize=9)
    ax.set_title(f"{mode} scaling — {grid} decomposition",
                 color=_INK, fontsize=11, loc="left")
    handles, labels = ax.get_legend_handles_labels()
    handles += [Line2D([], [], color=_INK2, linestyle="--", linewidth=1.4),
                Line2D([], [], color=_MUTED, linestyle=":", linewidth=1.2)]
    labels += ["model fit", "ideal"]
    ax.legend(handles, labels, frameon=False, fontsize=8,
              labelcolor=_INK2, loc="best")
    return fig


def _redist_figure(plt, grid: str, items: list) -> "object":
    fig, ax = plt.subplots(figsize=(6.4, 4.2))
    _style_axes(ax)
    ax.grid(True, axis="y", color=_GRIDLINE, linewidth=0.8)
    ax.grid(False, axis="x")
    ndevs = sorted({p["ndev"] for _, s in items
                    for p in s["redist"]["points"]})
    width = 0.8 / max(1, len(items))
    for slot, (_, s) in enumerate(items):
        color = _PALETTE[slot]
        total = {p["ndev"]: p["best_s"] for p in s["points"]}
        redist = {p["ndev"]: p["best_s"] for p in s["redist"]["points"]}
        xs, ex, comp = [], [], []
        for i, n in enumerate(ndevs):
            if n not in redist:
                continue
            xs.append(i + (slot - (len(items) - 1) / 2) * width)
            ex.append(redist[n])
            comp.append(max(0.0, total.get(n, redist[n]) - redist[n]))
        label = _series_label(s)
        # 2px surface gap between stacked segments and adjacent bars
        bar_kw = {"width": width * 0.92, "edgecolor": _SURFACE,
                  "linewidth": 1.5}
        ax.bar(xs, ex, color=color, label=f"{label} — redistribution",
               **bar_kw)
        ax.bar(xs, comp, bottom=ex, color=_tint(color),
               label=f"{label} — compute", **bar_kw)
    ax.set_xticks(range(len(ndevs)), [str(n) for n in ndevs])
    ax.set_xlabel("devices", color=_INK2, fontsize=9)
    ax.set_ylabel("wall time (s)", color=_INK2, fontsize=9)
    ax.set_title(f"redistribution vs compute — {grid} decomposition",
                 color=_INK, fontsize=11, loc="left")
    ax.legend(frameon=False, fontsize=8, labelcolor=_INK2, loc="best")
    return fig


def render_scaling_figures(bench: dict, outdir: str | Path) -> list[Path]:
    """Render a bench-v3 record (``normalize_bench.normalize_scaling``)
    into paper-style scaling + redistribution-split figures; returns the
    written paths (SVG and PNG per figure)."""
    plt = _mpl()
    outdir = Path(outdir)
    outdir.mkdir(parents=True, exist_ok=True)

    groups: dict[tuple, list] = {}
    splits: dict[str, list] = {}
    for name in sorted(bench.get("series") or {}):
        s = bench["series"][name]
        if s.get("points"):
            groups.setdefault((s.get("mode"), s.get("grid")), []).append(
                (name, s))
        if s.get("redist", {}).get("points"):
            splits.setdefault(s.get("grid"), []).append((name, s))

    paths = []
    for (mode, grid), items in sorted(groups.items()):
        # hues are assigned by slot order within a figure; past the
        # validated eight, fold the tail into one figure-level overflow
        items = items[:len(_PALETTE)]
        fig = _scaling_figure(plt, mode, grid, items)
        paths += _save(fig, outdir, f"scaling_{mode}_{grid}")
        plt.close(fig)
    for grid, items in sorted(splits.items()):
        items = items[:len(_PALETTE) // 2]
        fig = _redist_figure(plt, grid, items)
        paths += _save(fig, outdir, f"redistribution_split_{grid}")
        plt.close(fig)
    return paths


def main(which=None):
    ART.mkdir(parents=True, exist_ok=True)
    names = which or list(ALL)
    for name in names:
        rows = ALL[name]()
        (ART / f"{name}.json").write_text(json.dumps(rows, indent=1))
        # paper-claim check: fused redistribution <= traditional (per ndev)
        summary = {}
        for r in rows:
            if r["measure"] != "redistribution":
                continue
            key = r["ndev"]
            summary.setdefault(key, {})[r["method"]] = r["best_s"]
        for ndev, d in sorted(summary.items()):
            if {"fused", "traditional"} <= set(d):
                ratio = d["traditional"] / d["fused"]
                print(f"{name}: ndev={ndev} redistribution "
                      f"traditional/fused = {ratio:.2f}x", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or None)

"""§Roofline: three-term analysis for every (arch x shape x mesh) cell.

Reads the dry-run artifacts (benchmarks/artifacts/dryrun/*.json) and emits
the roofline table used in EXPERIMENTS.md:

  compute_s    = HLO_FLOPs_global   / (chips * 197e12)     [bf16 peak]
  memory_s     = HLO_bytes_global   / (chips * 819e9)      [HBM]
  collective_s = coll_bytes_global  / (chips * 50e9)       [ICI]

with HLO_* taken from the trip-count-aware accounting (launch/hlo_account),
globalized as per-device * chips.  MODEL_FLOPS = 6*N(_active)*D tokens.

"roofline fraction" = ideal_model_time / dominant_term: how close the cell
would run to peak if only the dominant resource were the limit.  The perf
loop (EXPERIMENTS.md §Perf) drives the dominant term down.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
ART = REPO / "benchmarks" / "artifacts" / "dryrun"

PEAK = 197e12
HBM = 819e9
ICI = 50e9

#: fraction of the non-dominant terms a well-pipelined schedule hides under
#: the dominant one (cf. the pipelined FFT exchange in core/redistribute.py:
#: all but the first slice's collective overlaps compute)
OVERLAP_EFF = 0.9


def overlap_time(compute_s, memory_s, collective_s, efficiency=OVERLAP_EFF):
    """Overlap-aware wall-time model.  The three terms are independent
    hardware pipes (MXU, HBM, ICI): a serial schedule pays their sum, a
    perfectly pipelined one pays only the max.  Real schedules land in
    between — ``efficiency`` is the fraction of the non-dominant terms that
    overlap hides (1.0 = perfect, 0.0 = serial)."""
    serial = compute_s + memory_s + collective_s
    dominant = max(compute_s, memory_s, collective_s)
    return dominant + (serial - dominant) * (1.0 - efficiency)


def term_seconds(rec):
    chips = rec["chips"]
    acct = rec.get("acct", {})
    fl = acct.get("flops_per_device", 0.0)
    hb = acct.get("hbm_bytes_per_device", 0.0)
    co = acct.get("collectives_per_device", {}).get("total", 0.0)
    return {
        "compute_s": fl / PEAK,
        "memory_s": hb / HBM,
        "collective_s": co / ICI,
        "chips": chips,
        "hlo_flops_global": fl * chips,
        "hbm_bytes_global": hb * chips,
        "coll_bytes_global": co * chips,
    }


def model_flops(rec):
    tokens = rec["batch"] * (rec["seq"] if rec["kind"] in ("train", "prefill") else 1)
    mult = 6.0 if rec["kind"] == "train" else 2.0   # fwd+bwd+upd vs fwd only
    return mult * rec["active_params"] * tokens


def analytic_min_bytes(rec):
    """Analytic LOWER bound on per-device HBM traffic (perfect fusion):
    params/opt-state movement + one activation-checkpoint stream + caches.
    The HLO-derived term is an upper bound (CPU fusion granularity); the
    truth for a TPU build lies between — both are reported."""
    chips = rec["chips"]
    p = rec["params"]
    tokens = rec["batch"] * rec["seq"]
    if rec["kind"] == "train":
        # read p (bf16, fwd+bwd gathers) + rw fp32 m/v + write p + grads
        param_traffic = p * (2 + 2 + 16 + 4) / chips
        act = 4 * tokens * _d_model(rec) * 2 / chips     # stash w+r, bf16, ~2x
        return param_traffic + act
    if rec["kind"] == "prefill":
        return p * 2 / chips + 4 * tokens * _d_model(rec) * 2 / chips
    # decode: read all (active) params + read the cache once
    cache = rec.get("cache_bytes", 0) or 2 * rec["batch"] * rec["seq"] * _d_model(rec) / 8
    return rec["active_params"] * 2 / chips + cache / chips


_DM = {"glm4_9b": 4096, "stablelm_12b": 5120, "nemotron_4_15b": 6144,
       "qwen2_72b": 8192, "deepseek_v2_lite_16b": 2048, "phi35_moe_42b": 4096,
       "seamless_m4t_medium": 1024, "llava_next_34b": 7168,
       "zamba2_2p7b": 2560, "falcon_mamba_7b": 4096}


def _d_model(rec):
    return _DM.get(rec["arch"], 4096)


def suggest(dom, rec):
    k = rec["kind"]
    if dom == "collective_s":
        return ("overlap FSDP gathers with layer compute / shrink payload "
                "(reduce-scatter grads in bf16, 2D-shard big tables)")
    if dom == "memory_s":
        if k == "decode":
            return "decode is cache-bandwidth-bound: shrink KV (MLA/GQA/quant) or batch more requests"
        return "raise arithmetic intensity: fuse elementwise chains, larger microbatch, remat less"
    return "compute-bound: this is the target regime; chase MXU util (tile sizes, bf16 paths)"


def analyze(mesh_filter="single"):
    rows = []
    for path in sorted(ART.glob("*.json")):
        rec = json.loads(path.read_text())
        if rec["mesh"] != mesh_filter or rec.get("sp_mode", "none") != "none":
            continue
        if rec.get("opt") or rec.get("flags"):
            continue  # §Perf variants are left out of the baseline table
        t = term_seconds(rec)
        mf = model_flops(rec)
        ideal = mf / (t["chips"] * PEAK)
        dom = max(("compute_s", "memory_s", "collective_s"), key=lambda k: t[k])
        bound = max(t["compute_s"], t["memory_s"], t["collective_s"])
        serial_s = t["compute_s"] + t["memory_s"] + t["collective_s"]
        overlap_s = overlap_time(t["compute_s"], t["memory_s"], t["collective_s"])
        rows.append({
            "arch": rec["arch"], "shape": rec["shape"], "kind": rec["kind"],
            **{k: t[k] for k in ("compute_s", "memory_s", "collective_s")},
            "memory_lb_s": analytic_min_bytes(rec) / HBM,
            "serial_s": serial_s,
            "overlap_s": overlap_s,
            "overlap_gain": serial_s / overlap_s if overlap_s else 0.0,
            "dominant": dom.replace("_s", ""),
            "model_flops": mf,
            "hlo_flops": t["hlo_flops_global"],
            "useful_ratio": mf / t["hlo_flops_global"] if t["hlo_flops_global"] else 0.0,
            "roofline_frac": ideal / bound if bound else 0.0,
            "next_move": suggest(dom, rec),
        })
    return rows


def to_markdown(rows):
    head = ("| arch | shape | compute s | memory s (hlo / lb) | collective s | "
            "overlap s (serial) | dominant | MODEL/HLO flops | roofline frac |\n"
            "|---|---|---|---|---|---|---|---|---|")
    out = [head]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} / {r['memory_lb_s']:.3e} | "
            f"{r['collective_s']:.3e} | "
            f"{r['overlap_s']:.3e} ({r['serial_s']:.3e}) | {r['dominant']} | "
            f"{r['useful_ratio']:.2f} | {r['roofline_frac']:.2f} |")
    return "\n".join(out)


def main(argv=None):
    mesh = (argv or sys.argv[1:] or ["single"])[0]
    rows = analyze(mesh)
    if not rows:
        print(f"no artifacts for mesh={mesh} under {ART} — run "
              f"`python -m repro.launch.dryrun --all --mesh {mesh}` first")
        return
    print(to_markdown(rows))
    out = REPO / "benchmarks" / "artifacts" / f"roofline_{mesh}.json"
    out.write_text(json.dumps(rows, indent=1))
    print(f"\n[{len(rows)} cells] -> {out}")
    worst = sorted(rows, key=lambda r: r["roofline_frac"])[:3]
    collb = [r for r in sorted(rows, key=lambda r: -r["collective_s"])][:3]
    print("\nworst roofline fraction:", [(r["arch"], r["shape"]) for r in worst])
    print("most collective-bound:", [(r["arch"], r["shape"]) for r in collb])


if __name__ == "__main__":
    main()

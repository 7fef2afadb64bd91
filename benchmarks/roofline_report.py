"""Aggregate the dry-run JSONs into the §Roofline table (and CSV)."""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks.common import CSV

DRYRUN = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"


def collect():
    rows = []
    for f in sorted(DRYRUN.glob("*.json")):
        rec = json.loads(f.read_text())
        rows.append(rec)
    return rows


def run(csv: CSV):
    rows = collect()
    ok = [r for r in rows if r.get("status") == "ok"]
    skipped = [r for r in rows if r.get("status") == "skipped"]
    errors = [r for r in rows if r.get("status") == "error"]
    for r in ok:
        rf = r["roofline"]
        bound = max(rf["compute_s"], rf["memory_s"], rf["collective_s"])
        frac = rf["compute_s"] / bound if bound else 0.0
        csv.emit(
            f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}",
            bound * 1e6,
            f"dominant={rf['dominant']};compute_s={rf['compute_s']:.4g};"
            f"memory_s={rf['memory_s']:.4g};collective_s={rf['collective_s']:.4g};"
            f"compute_frac_of_bound={frac:.3f};useful={r['useful_flops_ratio']:.3f};"
            f"hbm_gb={r['hbm_per_device_gb']:.1f}",
        )
    csv.emit(
        "roofline/summary", 0.0,
        f"ok={len(ok)};skipped={len(skipped)};errors={len(errors)}",
    )


if __name__ == "__main__":
    from repro.devices import enable_compile_cache

    enable_compile_cache()
    run(CSV())

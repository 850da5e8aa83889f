"""Aggregate all BENCH_r*.json into a BENCH_INDEX.md trajectory table.

Each PR's bench evidence lands as one JSON line in a `BENCH_rNN.json`
at the repo root (`make bench-*` targets), but the files are
heterogeneous one-offs — unreadable as a trajectory. This script renders
the one-row-per-round index: round, bench mode, headline metric, and the
claim the round's PR made. Shape-specific extractors keep the headline
honest per mode; an unknown shape degrades to its first numeric field
rather than being dropped, so a new bench is never invisible in the
index (it just gets a generic row until an extractor lands here).

Run: python scripts/bench_index.py   (or `make bench-index`)
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _num(value, digits=2):
    return round(float(value), digits)


def _extract(data: dict):
    """(mode, headline, claim) for one bench payload."""
    if "detection_on" in data:
        off = data["detection_off"]["p95_ttft_ms"]
        on = data["detection_on"]["p95_ttft_ms"]
        return ("failslow",
                f"p95 TTFT {off} → {on} ms "
                f"({data.get('p95_ttft_speedup')}x)",
                "fail-slow replica detected + replaced: detection-on "
                "p95 recovers; zero drops, zero error-path redispatches")
    if data.get("mode") == "kv_tier":
        tier = data.get("host_tier", {})
        un = tier.get("untiered", {}).get("served_from_cache_rate")
        ti = tier.get("tiered", {}).get("served_from_cache_rate")
        fetch = data.get("fetch_vs_reprefill", {})
        return ("kv-tier",
                f"cache-served rate {un} → {ti} at fixed device bytes",
                f"host KV tier revives evicted prefixes; ring-move "
                f"fetch {fetch.get('speedup_p50', '?')}x vs re-prefill")
    if "journal" in data and "cold" in data:
        j, c = data["journal"], data["cold"]
        return ("reconcile",
                f"recovery {j.get('recovery_s')}s vs "
                f"{c.get('recovery_s')}s cold",
                f"journaled reconcile adopts the live fleet "
                f"({j.get('orphaned_jobsets')} orphans vs "
                f"{c.get('orphaned_jobsets')} cold)")
    if "cold_join" in data and "prewarmed_join" in data:
        cold = data["cold_join"]["p95_ttft_ms"]
        warm = data["prewarmed_join"]["p95_ttft_ms"]
        return ("fleet-elastic",
                f"join p95 TTFT {cold} → {warm} ms pre-warmed",
                "pre-warmed ring join + SLO held through a pod "
                "preemption")
    if data.get("mode") == "prefill_kernel":
        kern = data.get("prefill_kernel", {}).get("kernel", {})
        return ("prefill-kernel",
                f"warm p50 TTFT {kern.get('warm_p50_ttft_ms')} ms, "
                f"hit rate {kern.get('prefix_hit_rate')}",
                "paged prefill kernel + int8 KV pages at parity")
    if data.get("mode") == "reqtrace":
        return ("reqtrace",
                f"p50 overhead ratio "
                f"{data.get('overhead_ratio_p50_ttft')}",
                "request forensics (phase ledger + exemplars) within "
                "noise of off")
    if data.get("mode") == "spec":
        on = data.get("spec_on", {})
        return ("spec",
                f"{data.get('speedup')}x decode tokens/s at acceptance "
                f"{on.get('acceptance_rate')} "
                f"(adversarial {data.get('adversarial_ratio')}x)",
                "in-engine speculative decoding on the paged kernel "
                "path: exact greedy parity in every arm, parked gate "
                "costs nothing")
    if "promoted" in data and "detection_wall_s" in data:
        return ("canary",
                f"drift→promotion {data.get('detection_to_promotion_s')}"
                f"s, stable overhead {data.get('stable_overhead_ratio')}",
                "continuous fine-tune→canary→promote loop closed")
    if "metric" in data and "value" in data:
        return (data["metric"],
                f"{data['value']} {data.get('unit', '')}".strip()
                + (f" ({data['vs_baseline']}x vs baseline)"
                   if data.get("vs_baseline") else ""),
                "goodput/badput attribution A/B")
    if "multi_tokens_per_sec" in data:
        return ("lora",
                f"{data.get('throughput_ratio')}x vs sequential "
                f"merged-weights swaps",
                "multi-tenant LoRA: batched adapters beat engine swaps")
    if "autoscaled" in data and "baseline" in data:
        base = data["baseline"].get("peak_p95_ttft_ms")
        auto = data["autoscaled"].get("peak_p95_ttft_ms")
        return ("autoscale",
                f"peak p95 TTFT {base} → {auto} ms",
                "closed scrape→scale loop meets the SLO the static "
                "fleet violates")
    if "policies" in data:
        pol = data["policies"]
        aff = pol.get("affinity", {}).get("prefix_hit_rate")
        ran = pol.get("random", {}).get("prefix_hit_rate")
        return ("fleet-routing",
                f"hit rate {ran} random → {aff} affinity "
                f"({data.get('hit_rate_ratio')}x)",
                "prefix-affinity routing keeps hot prefixes "
                "cache-resident per ring owner")
    # unknown shape: surface the first numeric scalar rather than
    # dropping the round from the trajectory
    for key, value in data.items():
        if isinstance(value, (int, float)) and not isinstance(
                value, bool):
            return ("?", f"{key}={value}", "(no extractor for this "
                    "bench shape — add one in scripts/bench_index.py)")
    return ("?", "-", "(unparseable payload)")


def build_index(root: Path = ROOT) -> str:
    rows = []
    for path in sorted(root.glob("BENCH_r*.json")):
        match = re.fullmatch(r"BENCH_r(\d+)\.json", path.name)
        if not match:
            continue
        rnd = int(match.group(1))
        text = path.read_text().strip()
        try:
            # whole file first (pretty-printed driver stubs), then the
            # last line (bench scripts log above their one JSON line)
            try:
                data = json.loads(text)
            except ValueError:
                data = json.loads(text.splitlines()[-1])
        except (ValueError, IndexError):
            rows.append((rnd, path.name, "?", "-", "(invalid JSON)"))
            continue
        mode, headline, claim = _extract(data)
        rows.append((rnd, path.name, mode, headline, claim))
    lines = [
        "# Bench trajectory",
        "",
        "One row per PR round's bench evidence (`BENCH_rNN.json` at the"
        " repo root,",
        "written by the `make bench-*` targets). Regenerate with"
        " `make bench-index`.",
        "",
        "| round | file | bench | headline | claim |",
        "|---|---|---|---|---|",
    ]
    for rnd, name, mode, headline, claim in sorted(rows):
        lines.append(
            f"| {rnd} | `{name}` | {mode} | {headline} | {claim} |")
    lines.append("")
    return "\n".join(lines)


def main(argv=None):
    out = ROOT / "BENCH_INDEX.md"
    content = build_index()
    out.write_text(content)
    count = content.count("\n| ") - 1  # header separator row
    print(f"bench-index: {max(0, count)} round(s) -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

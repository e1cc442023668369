"""§Roofline table assembler: reads experiments/dryrun/*.json and prints
the per-(arch x shape x mesh) three-term roofline table (markdown)."""
from __future__ import annotations

import argparse
import json
import pathlib


def fmt_s(v):
    if v >= 1:
        return f"{v:.2f}s"
    if v >= 1e-3:
        return f"{v*1e3:.1f}ms"
    return f"{v*1e6:.0f}us"


def load_rows(dirpath="experiments/dryrun", pod="pod1", tag=None):
    suffix = f"__{pod}.{tag}.json" if tag else f"__{pod}.json"
    rows = []
    for f in sorted(pathlib.Path(dirpath).glob("*.json")):
        if not f.name.endswith(suffix):
            continue
        rows.append(json.load(open(f)))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--pod", default="pod1")
    ap.add_argument("--tag", default=None)
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    rows = load_rows(args.dir, args.pod, args.tag)
    print("| arch | shape | compute | memory | collective | dominant |"
          " useful | MFU-bound |")
    print("|---|---|---|---|---|---|---|---|")
    for r in rows:
        if r.get("status") == "skipped":
            print(f"| {r['arch']} | {r['shape']} | — | — | — | skipped |"
                  f" — | — |")
            continue
        if r.get("status") != "ok":
            print(f"| {r['arch']} | {r['shape']} | — | — | — | ERROR | — | — |")
            continue
        t = r["roofline"]
        print(
            f"| {r['arch']} | {r['shape']} | {fmt_s(t['compute_s'])} | "
            f"{fmt_s(t['memory_s'])} | {fmt_s(t['collective_s'])} | "
            f"{t['dominant']} | {t['useful_ratio']:.2f} | "
            f"{t['mfu_bound']*100:.2f}% |"
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

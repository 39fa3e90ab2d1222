"""Perf probe: compile one dry-run cell and print the flops breakdown by
op_name (+ roofline terms). The 'profiler' for the §Perf loop.

Usage: python -m repro.launch.perfprobe --arch granite-20b --shape train_4k
"""
import argparse

from repro.launch import dryrun as dr
from repro.launch.hlo_analysis import flops_breakdown


def main():
    dr.force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi", action="store_true")
    ap.add_argument("--attn", default=None)
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--dump-hlo", default=None)
    args = ap.parse_args()

    # reuse run_cell but keep the compiled text
    import json
    import jax
    orig_analyze = dr.analyze_hlo
    captured = {}

    def capture(text):
        captured["hlo"] = text
        return orig_analyze(text)

    dr.analyze_hlo = capture
    res = dr.run_cell(args.arch, args.shape, multi_pod=args.multi,
                      attn=args.attn)
    print(json.dumps(res.get("roofline", res), indent=2))
    print({k: f"{v:.3e}" for k, v in res.get("hlo", {}).items()
           if k.startswith("coll_") and v})
    ma = res.get("memory_analysis", {})
    print(f"argbytes/dev={ma.get('argument_size')} "
          f"temp/dev={ma.get('temp_size')}")
    total = res["hlo"]["matmul_flops"]
    print(f"\nper-device matmul flops: {total:.3e}; breakdown:")
    for name, fl in flops_breakdown(captured["hlo"], top=args.top):
        print(f"  {fl:12.3e} ({100*fl/total:5.1f}%)  {name[:110]}")
    if args.dump_hlo:
        open(args.dump_hlo, "w").write(captured["hlo"])


if __name__ == "__main__":
    main()

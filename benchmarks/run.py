# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
import argparse
import json
import os
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter")
    ap.add_argument("--fast", action="store_true",
                    help="skip the slower placement sweeps")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a per-suite run record (status, seconds, "
                         "error, rows) to PATH")
    args = ap.parse_args()

    from . import common  # noqa: F401  (puts src/ on sys.path)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (copartition, deploy_e2e, device_search, fault_replace,
                   multichip, multilevel, noc_eval, paper_figs, ppo_pipeline,
                   roofline, service, spike_kernel, tpu_placement)

    benches = [
        ("table1", paper_figs.table1_eer),
        ("fig4", paper_figs.fig4_partition),
        ("fig9", paper_figs.fig9_pipeline),
        ("spike_kernel", spike_kernel.spike_kernel),
        ("roofline", roofline.roofline),
        ("noc_eval", noc_eval.noc_eval),
        ("ppo_pipeline", ppo_pipeline.ppo_pipeline),
        ("deploy_e2e", deploy_e2e.deploy_e2e),
        ("device_search", device_search.device_search),
        ("multilevel", multilevel.multilevel),
        ("multichip", multichip.multichip),
        ("copartition", copartition.copartition),
        ("fault_replace", fault_replace.fault_replace),
        ("service", service.service),
        ("fig6", paper_figs.fig6_placement_32),
        ("fig7_11", paper_figs.hotspots),
        ("fig10", paper_figs.fig10_vs_policy),
        ("fig8", paper_figs.fig8_placement_64),
        ("tpu_placement", tpu_placement.tpu_placement),
    ]
    # noc_eval / ppo_pipeline time the slow seed paths (reference loop, Python
    # spiral); deploy_e2e / multichip sweep full placement searches per model
    # x objective (multichip includes a PPO run on 64 cores); fault_replace
    # replays minute-scale scenario sweeps on the 64-core fabric (the nightly
    # job runs it as its own step, so --fast skipping it avoids a double run);
    # device_search repeats full-budget searches for latency percentiles;
    # multilevel repeats a 200k-iteration flat SA reference and places a
    # 16k-node graph (the nightly job runs the full sweep as its own step);
    # service repeats dozens of full-budget cold deployments for the cold /
    # warm / fused latency percentiles (nightly runs its full sweep too)
    fast_skip = {"fig8", "noc_eval", "ppo_pipeline", "deploy_e2e", "multichip",
                 "fault_replace", "device_search", "multilevel", "service"}
    print("name,us_per_call,derived")
    suites = []          # per-suite run records (the --json artifact)
    failed = []
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        if args.fast and name in fast_skip:
            continue
        t0 = time.time()
        rec = {"suite": name, "status": "ok", "rows": [], "error": None}
        try:
            rows = fn()
            for (rname, us, derived) in rows:
                print(f"{rname},{us:.1f},{derived}")
                rec["rows"].append({"name": rname, "us_per_call": float(us),
                                    "derived": str(derived)})
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
            rec["status"] = "error"
            rec["error"] = f"{type(e).__name__}: {e}"
            print(f"{name},0.0,ERROR {type(e).__name__}: {e}")
        rec["seconds"] = round(time.time() - t0, 3)
        suites.append(rec)
        sys.stderr.write(f"[bench {name}: {rec['seconds']:.1f}s]\n")

    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump({"suites": suites,
                       "n_failed": len(failed), "failed": failed}, f,
                      indent=2)
        sys.stderr.write(f"[bench record: {args.json}]\n")
    # a loud final verdict either way — a failing suite must not scroll away
    # as one CSV row in the middle of the output
    n = len(suites)
    if failed:
        print(f"# FAILED {len(failed)}/{n} suites: {', '.join(failed)}")
        sys.exit(1)
    print(f"# OK {n}/{n} suites")


if __name__ == '__main__':
    main()

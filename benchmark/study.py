"""python -m benchmark.study --workload <name>
    --seconds <s> --seeds a,b,c [--trace 1] [--fault f] [--tag t]

Runs one cell several times, one process per run (as the driver does),
and writes every run's diag and result lines to
chiprun_out/study_<tag>.jsonl with a one-line summary per run on
stdout: the noise study and the proof sets are made with it.  The
parent never touches jax (a chip belongs to one process)."""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmark.study")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--tag", default=None)
    args = ap.parse_args(argv)
    tag = args.tag or args.workload
    os.makedirs("chiprun_out", exist_ok=True)
    path = os.path.join("chiprun_out", f"study_{tag}.jsonl")
    rc_all = 0
    with open(path, "a") as log:
        for seed in [int(s) for s in args.seeds.split(",")]:
            cmd = [sys.executable, "-m", "benchmark.run", "--workload",
                   args.workload, "--seed", str(seed), "--seconds",
                   str(args.seconds), "--trace", str(args.trace)]
            if args.fault:
                cmd += ["--fault", args.fault]
            t0 = time.monotonic()
            p = subprocess.run(cmd, capture_output=True, text=True)
            wall = time.monotonic() - t0
            lines = p.stdout.strip().splitlines()
            diag = next((json.loads(ln[5:]) for ln in lines
                         if ln.startswith("diag ")), None)
            try:
                result = json.loads(lines[-1]) if lines else None
            except ValueError:
                result = None
            rec = {"workload": args.workload, "seed": seed,
                   "seconds": args.seconds, "trace": args.trace,
                   "fault": args.fault, "rc": p.returncode,
                   "wall_s": round(wall, 2), "diag": diag,
                   "result": result}
            if p.returncode != 0 or result is None:
                rec["stderr_tail"] = p.stderr[-3000:]
                rc_all = 1
            log.write(json.dumps(rec) + "\n")
            log.flush()
            print(summary(rec), flush=True)
    return rc_all


def summary(rec: dict) -> str:
    r, d = rec["result"], rec["diag"]
    if r is None or d is None:
        return (f"{rec['workload']} seed {rec['seed']} rc {rec['rc']} "
                f"NO RESULT: {rec.get('stderr_tail', '')[-800:]}")
    m = {k: round(v["value"], 3) for k, v in r["metrics"].items()}
    return (f"{rec['workload']} seed {rec['seed']} wall {rec['wall_s']} "
            f"correct {r['correct']} {m} | compiles "
            f"{d['window_jax']['compile_events']} miss "
            f"{d['window_jax']['cache_misses']} gc {d['gc']['collections']}"
            f" {d['gc']['pause_ms']} late {d['ticker']['worst_late_ms']} "
            f"rss {d['rss_gb']} fill {d['batch_fill']} epoch "
            f"{d['osdmap_epoch']} scrubs {d['scrubs']} peer "
            f"{d['peering_events']} cv {round(d['per_second_MB']['cv'], 4)} "
            f"min/max {d['per_second_MB']['min']:.0f}/"
            f"{d['per_second_MB']['max']:.0f} load {d['loadavg_1m_t0']:.2f}"
            f" warm {d['warm']} failed {r['failed']} lat {d.get('lat_ms')}"
            f" stalls {d['ticker'].get('stalls_over_100ms')} phases "
            f"{d.get('setup_phases_s')}")


if __name__ == "__main__":
    sys.exit(main())

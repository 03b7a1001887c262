"""H7 A/B driver: per-round dispatch vs the scanned super-step on the
packed cross-silo mesh path, at two silo counts. (_bench_crosssilo warms
two full passes — see docs/mfu_experiments.md H7 pitfall #2.)

Each cell is a whole _bench_crosssilo run (warm-up passes, then one
timed pass); the fixed per-round overhead is the weak-scaling intercept
(docs/perf.md: T(c) = a + b*c, a ~ 27.5 ms at r4), so the super-step's
win should be ~a*(H-1)/H per round, largest in relative terms at small c.

Usage: python tools/superstep_ab.py [H] [clients ...]
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv):
    h = int(argv[0]) if argv else 5
    clients = [int(c) for c in argv[1:]] or [8, 32]

    from fedml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from bench import _bench_crosssilo

    out = {}
    for c in clients:
        row = {}
        for tag, hh in (("per_round", "1"), (f"superstep_h{h}", str(h))):
            os.environ["BENCH_CS_SUPERSTEP"] = hh
            r = _bench_crosssilo(False, "resnet56", 5, 64, clients_override=c)
            row[tag] = {"rounds_per_sec": r["rounds_per_sec"],
                        "round_ms": round(1e3 / r["rounds_per_sec"], 1),
                        "real_img_s": r["images_per_sec"]}
            print(json.dumps({"clients": c, tag: row[tag]}), flush=True)
        a, b = row["per_round"], row[f"superstep_h{h}"]
        row["saved_ms_per_round"] = round(
            1e3 / a["rounds_per_sec"] - 1e3 / b["rounds_per_sec"], 2)
        out[str(c)] = row
    print(json.dumps({"h": h, "results": out}))


if __name__ == "__main__":
    main(sys.argv[1:])

"""The knee of an open-loop serving cell, found once on the chip: the
cell's mix at each offered rate, in one process, printing the tails, the
rate served and the backlog left at the close of each window.

    python3 -m bench.sweep --workload <cell> --rates 2,4,8,16 --seconds 20 --seed <n>

The knee is the highest rate whose backlog stays bounded and whose
served rate keeps up with the offered one; the cell then runs at 0.8 of
it, written into its mix file by hand.  Give each rate a window several
times a request's life: requests that outlive the window fill the slots
only after it, so a short window shows no queue above the knee.
"""

from __future__ import annotations

import argparse
import copy
import json
import time

from bench import run as bench_run
from bench import spec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    base = spec.load_cell(args.workload)
    from bench import program

    program.use_compile_cache()
    bench_run.refuse_without_chips(base.chips)
    from bench import serve

    program.refuse_unless_kernels()
    compiles = bench_run.CompileCounter()
    for r in (float(x) for x in args.rates.split(",")):
        cell = copy.deepcopy(base)
        cell.traffic["arrivals"]["rate_per_s"] = r
        cell.traffic["drain_cap_s"] = 0.0
        t = time.monotonic()
        res = serve.run_cell(cell, args.seed, args.seconds, None, t,
                             compiles, check="none")
        judged = res["judged"]
        out_tok = sum(len(j.req.tokens) for j in judged)
        print(json.dumps({
            "rate_per_s": r, "e2e": res["e2e"],
            "due": len(judged),
            "finished_in_window": sum(1 for j in judged if j.req.done),
            "backlog_at_close": res["backlog_at_close"],
            "tokens_of_due_requests": out_tok,
            "setup_s": res["setup_s"], "wall_s": time.monotonic() - t,
        }, default=float), flush=True)


if __name__ == "__main__":
    main()

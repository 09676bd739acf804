#!/usr/bin/env python3
"""Find a serving cell's knee once: the highest offered rate it sustains.

    python3 benchmark/tools/knee_sweep.py --workload <cell> --rates 2,3,3.5,4,4.5,5 --seconds 45

One process, one server, one compile: each rate gets a window of
``--seconds`` and a drain, on the cell's own mix — the very schedule the
cell would offer at that rate. A rate is *sustained* when the time to first
token is level through the window (its median over the last third of the
arrivals is at most ``LEVEL`` times that over the middle third, or under
``NO_QUEUE`` of one request's own length: no queue to speak of) and the
drain after the window takes at most ``DRAIN`` times one request's own
length (its tokens times the median gap between them). The knee is the
highest sustained rate below the lowest that is not; it goes into the
cell's traffic file as ``knee_per_s``, and the cell offers ``load`` times
it. Not part of a benchmark run.
"""

import argparse
import json
import os
import sys
import time

LEVEL, NO_QUEUE, DRAIN = 1.5, 0.1, 1.25

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    os.environ.setdefault("WANDB_DISABLED", "1")
    import numpy as np

    from benchmark import harness, loadgen, serve_driver
    from trlx_tpu.inference.server import InferenceServer

    cell = harness.load_cell(args.workload)
    cf, t = cell["config_file"], cell["traffic_file"]
    harness.place_compile_cache()
    harness.require_chips(int(cell["chips"]))
    config = serve_driver.build_config(cell)
    server = InferenceServer(
        config, params=serve_driver.seeded_params(config, t.get("weights_seed", args.seed)),
        seed=loadgen.program_seed(args.seed),
    )
    spans, budget = harness.Spans(), t["max_new_tokens"]
    n_warm = int(t["warmup_requests"])
    warm = loadgen.draw_prompts(t["prompt_lengths"], n_warm, cf["vocab_size"], t["traffic_seed"], 0)
    serve_driver.drive(server, warm, np.zeros(n_warm), 0.0, 3600.0, spans, budget)
    knee, held = None, True
    for rate in (float(r) for r in args.rates.split(",")):
        arrivals = dict(t["arrivals"], knee_per_s=rate, load=1.0)
        due = loadgen.arrival_times(arrivals, args.seconds, t["traffic_seed"])
        prompts = loadgen.draw_prompts(
            t["prompt_lengths"], len(due), cf["vocab_size"], t["traffic_seed"], args.seed,
            order_seed=t.get("order_seed"))
        est = server.engine.stats
        occ0, steps0 = est.occupancy_sum, est.decode_steps
        out = serve_driver.drive(server, prompts, due, args.seconds, 120.0, spans, budget)
        seen = serve_driver.client_side(out, args.seconds)
        steps = est.decode_steps - steps0
        # by arrival: `done` is in the order requests finished
        ttft = [c.token_times[0] - c.due for c in sorted(out["done"], key=lambda c: c.due)
                if c.token_times]
        third = len(ttft) // 3
        thirds = [ttft[:third], ttft[third:2 * third], ttft[2 * third:]]
        p50 = lambda xs: float(np.median(xs)) * 1e3 if xs else None
        p95 = lambda xs: loadgen.percentile(xs, 95) * 1e3 if xs else None
        itl_p50 = float(np.median(seen["gaps"]))
        drain_s = out["ended_s"] - args.seconds
        own_s = budget * itl_p50  # one request's own length
        sustained = bool(
            not out["unfinished"]
            and p50(thirds[2]) <= max(LEVEL * p50(thirds[1]), NO_QUEUE * own_s * 1e3)
            and drain_s <= DRAIN * own_s
        )
        held = held and sustained
        if held:
            knee = rate
        print(json.dumps({
            "rate_per_s": rate, "requests": len(due), "unfinished": len(out["unfinished"]),
            "sustained": sustained,
            "tokens_per_s_in_window": seen["tokens_in_window"] / args.seconds,
            "offered_tokens_per_s": rate * budget,
            "drain_s": drain_s, "drain_limit_s": DRAIN * own_s,
            "ttft_p50_ms_by_third": [p50(x) for x in thirds],
            "ttft_p95_ms_by_third": [p95(x) for x in thirds],
            "ttft_p50_ms": p50(ttft), "ttft_p95_ms": p95(ttft),
            "itl_p50_ms": itl_p50 * 1e3, "itl_p95_ms": p95(seen["gaps"]),
            "itl_p99_ms": loadgen.percentile(seen["gaps"], 99) * 1e3,
            "long_gap_pct": 100.0 * float(np.mean(np.asarray(seen["gaps"]) > 2 * itl_p50)),
            "lag_p95_ms": p95(seen["lag"]),
            "slot_util": (est.occupancy_sum - occ0) / (steps * t["slots"]) if steps else 0.0,
            "decode_steps_per_s": steps / out["ended_s"],
        }), flush=True)
    print(json.dumps({"knee_per_s": knee, "rule": f"level x{LEVEL} or under {NO_QUEUE} of a request, drain x{DRAIN}"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

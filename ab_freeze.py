"""A/B: train phase with top-2 layer freezing vs full training.

(r5 correction of this header's claim: the reference as SHIPPED trains
all 12 layers — its PPO freezing block is commented out,
`accelerate_base_model.py:55-69`; `test_config.yml:5`'s
``num_layers_unfrozen: 2`` only sizes the hydra KL-ref branch. Full
training is therefore the FAITHFUL workload and the bench headline;
freezing is the work-avoidance capability this file measures the delta
of.) Round 4 made freezing real work-avoidance: stop_gradient on frozen
leaves (XLA dead-code-eliminates the backward below the branch point)
and optax.masked moments (frozen params carry no optimizer state or
Adam traffic).

This measures that delta in ONE session with the interleaved methodology
(bench_longctx.py / MEMORY.md): one trainer, the freezing swapped in
place (mask + optimizer + re-jitted train phase — fresh closures, so no
trace-cache aliasing), globally-unique shuffle seeds per timed call,
interleaved order across rounds, best-of-N, each window ended on a value
fetch.

Prints one JSON line with per-variant best ms and the speedup.
"""

import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("WANDB_DISABLED", "1")

import jax

from bench_collect_audit import force, make_bench_workload
from trlx_tpu.parallel import replicated
from trlx_tpu.trainer.common import (
    TrainState, make_optimizer, unfrozen_param_mask,
)


def main():
    cfg, tr, pipe, orch = make_bench_workload()
    orch.make_experience(cfg.method.num_rollouts, 0)  # fill the buffer once
    seed_counter = itertools.count(1)

    def set_unfrozen(k):
        """Swap the freezing boundary in place: mask, optimizer (+fresh
        opt state), and re-jitted train fns (fresh closures)."""
        cfg.model.num_layers_unfrozen = k
        tr.trainable_mask = unfrozen_param_mask(
            tr.state.params, k, tr._n_layers()
        )
        tr.tx = make_optimizer(cfg.train, cfg.train.total_steps,
                               tr.trainable_mask)
        opt_shapes = jax.eval_shape(tr.tx.init, tr.state.params)
        tr.opt_shardings = tr._shardings_for(opt_shapes)
        new_opt = jax.jit(tr.tx.init, out_shardings=tr.opt_shardings)(
            tr.state.params
        )
        tr.state = TrainState(
            params=tr.state.params, opt_state=new_opt, step=tr.state.step
        )
        tr.state_shardings = TrainState(
            params=tr.param_shardings, opt_state=tr.opt_shardings,
            step=replicated(tr.mesh),
        )
        tr._build_jitted_fns()

    def measure(n=4):
        ts = []
        for i in range(n + 2):  # first two absorb compile + relayout
            t0 = time.perf_counter()
            tr.train_on_buffer(seed=next(seed_counter))
            force(jax.tree_util.tree_leaves(tr.state.params)[0])
            ts.append((time.perf_counter() - t0) * 1000)
        return ts[2:]

    best = {"full": float("inf"), "frozen_top2": float("inf")}
    for rnd in range(2):
        order = (
            [(-1, "full"), (2, "frozen_top2")]
            if rnd % 2 == 0
            else [(2, "frozen_top2"), (-1, "full")]
        )
        for k, name in order:
            set_unfrozen(k)
            best[name] = min(best[name], min(measure()))

    full = best["full"]
    frozen = best["frozen_top2"]
    print(json.dumps({
        "metric": "train_phase_ms_32_updates_B16_T112_gpt2s",
        "full_ms": round(full, 1),
        "frozen_top2_ms": round(frozen, 1),
        "speedup": round(full / frozen, 3),
        "device_kind": jax.devices()[0].device_kind,
    }))


if __name__ == "__main__":
    main()

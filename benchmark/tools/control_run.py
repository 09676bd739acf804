#!/usr/bin/env python3
"""Read a cell's control: the cell as the command runs it, with a path one
precision below what its configuration states planted under the program.
``correct`` has to come out false.

    python3 benchmark/tools/control_run.py --workload serve-deepseekv3-reason1k \\
        --control float8_latent --seed <n> --seconds 10

One process, one run, the command's own result line as the last line: the
reading is ``checks["reference.sampled_logprob_rms"]``, and a configuration's
tolerance file keeps the readings under ``cheaper`` (each limit lies under the
smallest of them). A short window at the cell's own rate is enough: the
comparison is made on the idle server at the cell's own sizes once the window
has closed. Not part of a benchmark run; ``tests/benchmark`` runs each control
at a toy size.
"""

import argparse
import contextlib
import os
import sys
import time

T_START = time.time()
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


@contextlib.contextmanager
def float8_latent():
    """deepseek_v3: the latent row ``[c_kv | k_r]`` rounded to float8_e4m3fn
    as ``decode_attention`` is handed it, at admission and at every decode
    step, so the pool holds and every read sees fp8 values; weights, compute,
    the router and the softmax as they are."""
    import jax.numpy as jnp

    import trlx_tpu.models.deepseek_v3 as family

    inner = family.decode_attention

    def rounded(q, k_new, v_new, *args, **kwargs):
        return inner(q, k_new.astype(jnp.float8_e4m3fn).astype(k_new.dtype), v_new, *args, **kwargs)

    family.decode_attention = rounded
    try:
        yield
    finally:
        family.decode_attention = inner


CONTROLS = {"float8_latent": float8_latent}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True, choices=sorted(CONTROLS))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from benchmark.harness import NoAccelerator
    from benchmark.run import run_cell

    try:
        with CONTROLS[args.control]():
            line = run_cell(args.workload, args.seed, args.seconds, False, T_START)
    except NoAccelerator as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

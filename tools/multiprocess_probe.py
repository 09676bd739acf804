"""Standalone repro of the jax 0.4.x two-process startup-barrier abort.

``tests/test_multiprocess.py::test_two_process_sharded_ppo_step`` is
quarantined (``xfail(run=False)``) because
``multihost_utils.sync_global_devices`` aborts inside
``broadcast_one_to_all`` at the startup barrier for a two-process CPU
rendezvous in this container — library-level, before any repo logic
runs.  That quarantine is the first blocker of ROADMAP direction 1
(real multi-controller execution); until it lifts, the lockstep
auditor (``python -m trlx_tpu.analysis --lockstep``) is the stand-in
gate for N-host dispatch agreement.

This probe isolates the minimal trigger: two OS processes join one JAX
runtime via ``jax.distributed.initialize`` (coordinator on a localhost
port) and immediately call ``sync_global_devices("startup")`` followed
by a ``broadcast_one_to_all`` round-trip — the exact call pair
``parallel/distributed.py::barrier``/``broadcast_host_value`` make, with
no trainer, mesh, or model anywhere in the process.

Run::

    python tools/multiprocess_probe.py            # spawn 2 ranks, diagnose
    python tools/multiprocess_probe.py --procs 2  # explicit rank count

Expected output on this container's jaxlib (the bug present)::

    REPRODUCED: sync_global_devices aborted at the startup barrier
    ... (first error lines from the failing rank) ...

After a jaxlib bump that fixes the rendezvous the probe prints
``FIXED UPSTREAM`` — at which point the ``test_multiprocess.py``
quarantine, the ROADMAP entry, and this file can be retired, and
direction 1 unblocks.  Exit status: 0 for both the REPRODUCED and
FIXED UPSTREAM verdicts (the probe is informational, like
``tools/pp_miscompile_repro.py``); 1 only for an unexpected failure
shape (e.g. ranks hang past the timeout or die before the barrier),
which means the quarantine reason needs re-diagnosis, not retirement.
"""

import argparse
import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TIMEOUT = 300
_SENTINEL = "probe rank {rank}: barrier + broadcast ok"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env() -> dict:
    env = dict(os.environ)
    # one virtual CPU device per rank — the barrier needs no mesh; scrub
    # any single-process device-count flag this process inherited
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append("--xla_force_host_platform_device_count=1")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def worker(coordinator: str, num_processes: int, rank: int) -> None:
    """One rank: initialize, hit the startup barrier, broadcast once."""
    import jax

    # a CPU tool: the ranks rendezvous over virtual CPU devices whatever
    # the host offers — pinned before the first backend touch, same as
    # parallel/_mp_smoke.py
    jax.config.update("jax_platforms", "cpu")

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=rank,
    )
    assert jax.process_count() == num_processes, jax.process_count()

    from jax.experimental import multihost_utils

    # the abort site: barrier() delegates here when process_count > 1
    multihost_utils.sync_global_devices("startup")
    # the other half of the pair distributed.py leans on
    value = multihost_utils.broadcast_one_to_all(
        1234 if rank == 0 else -1
    )
    assert int(value) == 1234, value
    print(_SENTINEL.format(rank=rank), flush=True)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--procs", type=int, default=2)
    parser.add_argument(
        "--worker",
        nargs=3,
        metavar=("COORDINATOR", "NPROCS", "RANK"),
        help=argparse.SUPPRESS,
    )
    args = parser.parse_args()

    if args.worker:
        coordinator, nprocs, rank = args.worker
        worker(coordinator, int(nprocs), int(rank))
        return 0

    coordinator = f"127.0.0.1:{_free_port()}"
    env = _worker_env()
    procs = [
        subprocess.Popen(
            [
                sys.executable,
                os.path.abspath(__file__),
                "--worker",
                coordinator,
                str(args.procs),
                str(rank),
            ],
            env=env,
            cwd=REPO,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for rank in range(args.procs)
    ]
    outs = []
    hung = False
    try:
        for p in procs:
            out, _ = p.communicate(timeout=_TIMEOUT)
            outs.append(out)
    except subprocess.TimeoutExpired:
        hung = True
        for p in procs:
            p.kill()
            out, _ = p.communicate()
            outs.append(out)

    ok = not hung and all(p.returncode == 0 for p in procs)
    synced = all(
        _SENTINEL.format(rank=r) in out for r, out in enumerate(outs)
    )
    if ok and synced:
        print("FIXED UPSTREAM: sync_global_devices + broadcast_one_to_all")
        print(
            "completed across %d processes — retire the "
            "test_multiprocess.py quarantine, the ROADMAP entry, and "
            "this probe; direction 1 unblocks." % args.procs
        )
        return 0

    # classify the failure: the known bug aborts at/inside the barrier
    # AFTER distributed.initialize succeeded (ranks print nothing)
    joined = "\n".join(outs)
    barrier_abort = not hung and not synced
    if hung:
        print(
            "UNEXPECTED: ranks hung for %ds instead of aborting — "
            "re-diagnose before trusting the quarantine reason."
            % _TIMEOUT
        )
    elif barrier_abort:
        print("REPRODUCED: sync_global_devices aborted at the startup")
        print(
            "barrier (library-level, before any repo logic) — the "
            "test_multiprocess.py quarantine stands."
        )
    for rank, out in enumerate(outs):
        head = [ln for ln in out.splitlines() if ln.strip()][:8]
        if head:
            print(f"--- rank {rank} (rc={procs[rank].returncode}) ---")
            print("\n".join(head))
    if barrier_abort:
        return 0
    print(joined[-2000:] if len(joined) > 2000 else "", end="")
    return 1


if __name__ == "__main__":
    sys.exit(main())

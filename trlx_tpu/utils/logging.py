"""Metric logging: stdout JSON-lines always, wandb when available.

Re-design of the reference's wandb-only path
(``Accelerator(log_with="wandb")`` + ``init_trackers``,
`accelerate_base_model.py:38,78-92`): the tracker here is a thin host-side
sink — training stats arrive as plain dicts of floats (device scalars are
pulled once per log step, never inside jitted code). ``debug`` env disables
wandb as the reference does (`accelerate_base_model.py:88`).
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, Optional

from trlx_tpu.telemetry.tracer import monotonic
from trlx_tpu.utils import filter_non_scalars, get_git_tag


class Logger:
    def __init__(
        self,
        project_name: str = "trlx_tpu",
        run_name: str = "",
        config: Optional[Dict[str, Any]] = None,
        tags=(),
        use_wandb: Optional[bool] = None,
        stream=None,
        total_steps: Optional[int] = None,
    ):
        self.stream = stream or sys.stdout
        # the tracer's monotonic clock, not time.time(): logged "time"
        # deltas share the timebase of every span/Clock measurement
        self.start = monotonic()
        self._wandb = None
        # graceful degradation (docs/resilience.md): consecutive wandb
        # emission failures past this disable the tracker with one
        # stderr warning — a crash-looping/unreachable tracker must not
        # kill (or stall) a training run; stdout JSONL keeps flowing
        self._wandb_failure_limit = 3
        self._wandb_failures = 0
        # interactive tqdm progress line (reference shows a tqdm bar with a
        # live loss description, `accelerate_base_model.py:245-297`);
        # stderr-only, so stdout's JSON lines stay machine-parseable
        self._pbar = None
        self._total_steps = total_steps
        # rank-0 gating on multi-host pods (reference gates trackers on
        # accelerator.is_main_process, `accelerate_base_model.py:78`)
        from trlx_tpu.parallel.distributed import is_main_process

        self.is_main = is_main_process()
        if use_wandb is None:
            use_wandb = (
                self.is_main
                and os.environ.get("debug", "") == ""
                and os.environ.get("WANDB_DISABLED", "") not in ("1", "true")
            )
        if use_wandb:
            try:
                import wandb

                self._wandb = wandb.init(
                    project=project_name,
                    name=run_name or None,
                    config=config,
                    tags=[*tags, get_git_tag()],
                    mode=os.environ.get("WANDB_MODE", "offline"),
                )
            except Exception as e:
                # one visible line, not silence: a misconfigured tracker
                # (bad API key, unwritable dir, version clash) used to be
                # indistinguishable from wandb-not-installed — runs ended
                # with no curves and no clue why
                print(
                    f"warning: wandb init failed, logging to stdout only "
                    f"({type(e).__name__}: {e})",
                    file=sys.stderr,
                )
                self._wandb = None

    def log(self, stats: Dict[str, Any], step: Optional[int] = None) -> None:
        import jax

        # pull ALL device values in ONE transfer event — per-key float()
        # conversions would each block on their own device->host fetch.
        # Flattening the whole stats pytree (not just top-level entries)
        # catches device scalars nested under sub-dicts/lists too.
        if not self.is_main:
            return
        leaves, treedef = jax.tree_util.tree_flatten(stats)
        device_ix = [
            i for i, leaf in enumerate(leaves) if isinstance(leaf, jax.Array)
        ]
        if device_ix:
            fetched = jax.device_get([leaves[i] for i in device_ix])
            for i, v in zip(device_ix, fetched):
                leaves[i] = v
            stats = jax.tree_util.tree_unflatten(treedef, leaves)
        scalars = filter_non_scalars(stats)
        record = {"step": step, "time": round(monotonic() - self.start, 2), **scalars}
        if self._pbar is not None:
            # erase the live bar first: stdout and stderr often share the
            # terminal, and printing at the bar's cursor garbles both
            self._pbar.clear()
        print(json.dumps(record, default=float), file=self.stream, flush=True)
        self._wandb_emit(
            lambda: self._wandb.log(scalars, step=step), what="metrics"
        )
        self._update_progress(step, scalars)

    def _wandb_emit(self, emit, what: str) -> None:
        """Run one wandb emission with degradation: an exception never
        propagates into the train loop (the stdout JSONL line already
        landed), and repeated consecutive failures disable the tracker
        with a single warning instead of failing every step. Carries
        the ``logger.emit`` fault-injection site (resilience/chaos.py)."""
        if self._wandb is None:
            return
        from trlx_tpu.resilience import chaos

        try:
            chaos.check("logger.emit")
            emit()
            self._wandb_failures = 0
        except Exception as e:
            self._wandb_failures += 1
            if self._wandb_failures == 1:
                print(
                    f"warning: wandb {what} emission failed "
                    f"({type(e).__name__}: {e}); will keep trying",
                    file=sys.stderr,
                )
            if self._wandb_failures >= self._wandb_failure_limit:
                print(
                    f"warning: wandb emission failed "
                    f"{self._wandb_failures} times in a row — disabling "
                    "wandb for this run; metrics continue as stdout JSON "
                    "lines",
                    file=sys.stderr,
                )
                self._wandb = None

    def _update_progress(self, step, scalars) -> None:
        if not (hasattr(sys.stderr, "isatty") and sys.stderr.isatty()):
            return
        if self._pbar is None:
            try:
                from tqdm import tqdm
            except ImportError:
                return
            self._pbar = tqdm(
                total=self._total_steps, desc="train", dynamic_ncols=True
            )
        if step is not None:
            self._pbar.n = int(step)
        postfix = {}
        for key in ("losses/total_loss", "reward/mean", "exp/score_mean"):
            if key in scalars:
                postfix[key.split("/")[-1]] = f"{float(scalars[key]):.4f}"
        if postfix:
            self._pbar.set_postfix(postfix, refresh=False)
        self._pbar.refresh()

    def log_health_event(
        self, event: Dict[str, Any], step: Optional[int] = None
    ) -> None:
        """Emit one structured run-health event (telemetry/health.py) as
        a ``health_event`` JSON line on the metrics stream — greppable
        next to the stats rows that tripped it — plus a wandb counter
        bump so dashboards can alert on trips without parsing stdout."""
        if not self.is_main:
            return
        if self._pbar is not None:
            self._pbar.clear()  # same terminal-sharing guard as log()
        record = {
            "step": step,
            "time": round(monotonic() - self.start, 2),
            "health_event": event,
        }
        print(json.dumps(record, default=float), file=self.stream, flush=True)
        detector = event.get("detector", "unknown")
        self._wandb_emit(
            lambda: self._wandb.log(
                {f"health/event/{detector}": float(event.get("value", 1.0))},
                step=step,
            ),
            what="health event",
        )

    def log_samples(self, rows, columns, step: Optional[int] = None) -> None:
        """Log generated-sample tables (reference wandb Table,
        `accelerate_base_model.py:180-221`); stdout shows the first rows."""
        if not self.is_main:
            return
        if self._pbar is not None:
            self._pbar.clear()  # same terminal-sharing guard as log()
        for row in rows[:4]:
            printable = {c: str(v)[:120] for c, v in zip(columns, row)}
            print(json.dumps({"sample": printable}, default=str), file=self.stream)
        if self._wandb is not None:
            import wandb

            self._wandb_emit(
                lambda: self._wandb.log(
                    {"samples": wandb.Table(columns=list(columns), rows=[list(r) for r in rows])},
                    step=step,
                ),
                what="sample table",
            )

    def finish(self) -> None:
        if self._pbar is not None:
            self._pbar.close()
            self._pbar = None
        if self._wandb is not None:
            self._wandb.finish()

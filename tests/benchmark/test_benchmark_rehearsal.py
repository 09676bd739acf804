"""A tiny-size CPU rehearsal of ``benchmark/run.py``: one PPO cell and the
serving cell run end to end through the same code the chip runs, and the
last line holds exactly the contract's keys; asked to *measure* without a
TPU, the command fails and prints no result. The numbers of a rehearsal
are CPU numbers at a toy size and are asserted only for their form."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import harness
from benchmark.run import run_cell

TINY = {
    "gpt2": {"vocab_size": 96, "n_positions": 64, "n_embd": 32, "n_layer": 2, "n_head": 4},
    "gpt_neox": {"vocab_size": 96, "max_position_embeddings": 64, "hidden_size": 32,
                 "num_hidden_layers": 2, "num_attention_heads": 4, "intermediate_size": 128},
}


def shrunk(name):
    cell = harness.load_cell(name)
    cell["config_file"].update(TINY[cell["config_file"]["model_type"]])
    cell["mesh"] = {"dp": -1, "fsdp": 1, "tp": 1}  # tier-1's eight virtual CPU devices
    t = cell["traffic_file"]
    if t["driver"] == "ppo":
        t.update(seq_length=16, prompt_lengths={"dist": "uniform", "lo": 4, "hi": 16},
                 new_tokens=8, num_rollouts=16, chunk_size=16, batch_size=8, ppo_epochs=2,
                 ref_branch_layers=1, warmup_phases=2, trace_phases=2)
    else:
        t.update(seq_length=16, max_new_tokens=8, slots=16, admit_width=8, harvest_width=8,
                 prompt_lengths={"dist": "lognormal", "median": 8, "sigma": 0.5, "lo": 2, "hi": 16},
                 arrivals={"process": "poisson", "knee_per_s": 25.0, "load": 0.8}, warmup_requests=12,
                 drain_limit_s=30, trace_seconds=1)
    return cell


@pytest.fixture(autouse=True)
def quiet_program(monkeypatch, tmp_path):
    monkeypatch.setenv("WANDB_DISABLED", "1")
    # the rehearsal compiles from nothing like every tier-1 test: the
    # harness would otherwise place a persistent cache inside the checkout
    monkeypatch.setattr(harness, "place_compile_cache", lambda: "off")


def last_line(name, trace, capsys, seconds=1.0):
    line = run_cell(name, 2**31 + 77, seconds, trace, allow_cpu=True, cell=shrunk(name))
    said = capsys.readouterr()
    checks = [l for l in said.out.splitlines() if l.startswith("check ")]
    # the error stream ends with the same checks, each number beside its limit
    assert [l.split(":")[0] for l in said.err.splitlines()[-len(checks):]] == [
        l.split(":")[0] for l in checks]
    return json.loads(line), checks


@pytest.mark.parametrize("name,metrics", [
    ("ppo-gpt2m-tldr", {"ppo_samples_per_s", "setup_s"}),
    ("serve-pythia1b4-chat", {"serve_itl_p95_ms", "serve_tokens_per_s", "setup_s"}),
])
def test_end_to_end_line_has_exactly_the_contract_keys(name, metrics, capsys):
    out, checks = last_line(name, False, capsys)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert out["device"]["platform"] == "cpu"  # a rehearsal says what it ran on
    assert set(out["metrics"]) == metrics
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    # every sub-check printed its name, value and tolerance on an earlier line
    assert len(checks) >= 5 and all("value=" in c and "tolerance=" in c for c in checks)
    assert any(c.startswith("check accounting.compiles_in_window") for c in checks)
    assert any(c.startswith("check reference.sampled_logprob_rms") for c in checks)
    # and the result's last key holds each number compared beside its limit
    assert list(out["checks"]) == [c.split()[1].rstrip(":") for c in checks]
    rms = out["checks"]["reference.sampled_logprob_rms"]
    # (at this size `auto` resolves the PPO cell's cache to int8, the serve cell's is bf16)
    assert rms["limit"] in ("<= 0.011", "<= 0.035") and 0 < rms["value"] <= 0.035 and rms["ok"] is True
    assert all(c["ok"] for c in out["checks"].values())


def test_a_token_altered_where_it_is_produced_turns_correct_false(capsys, monkeypatch):
    """The rest of a run with the timed path broken underneath: every group
    the engine harvests carries tokens other than the ones it drew (and
    recorded log-probabilities for), and the reference comparison says so."""
    from trlx_tpu.inference.engine import ContinuousBatchingEngine

    harvest = ContinuousBatchingEngine._harvest_ready
    vocab = TINY["gpt_neox"]["vocab_size"]

    def altered(self):
        for group in harvest(self):
            yield dict(group, tokens=(group["tokens"] + 1) % (vocab - 1))

    monkeypatch.setattr(ContinuousBatchingEngine, "_harvest_ready", altered)
    out, _ = last_line("serve-pythia1b4-chat", False, capsys)
    assert out["correct"] is False
    wrong = out["checks"]["reference.sampled_logprob_rms"]
    assert wrong["ok"] is False and wrong["value"] > 10 * 0.011


def test_traced_line_reports_per_layer_metrics_of_the_cell(capsys):
    # the traced run offers the whole window (2 s at 20/s), not only the
    # profiler's slice (its last second)
    out, _ = last_line("serve-pythia1b4-chat", True, capsys, seconds=2.0)
    assert out["correct"] is True and out["attempted"] == 40 and out["failed"] == 0
    listed = {s["name"] for s in harness.load_layer_metrics("serve-pythia1b4-chat")}
    assert set(out["metrics"]) <= listed
    # program counters and host clocks read on any platform; the device
    # trace has no TPU plane here, so its readers return nothing
    assert {"serve_queue_wait_p95_ms", "engine_slot_util", "loadgen_lag_p95_ms",
            "serve_itl_p99_ms", "serve_ttft_p95_ms"} <= set(out["metrics"])
    assert "decode_serve_roofline" not in out["metrics"] and "busy_s" not in out["device"]


def test_the_command_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.REPO, "benchmark", "run.py"), "--workload",
         "ppo-gpt2m-tldr", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=harness.REPO, timeout=300,
    )
    assert proc.returncode != 0
    assert "refused" in proc.stderr
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]

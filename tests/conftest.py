"""Test harness: force an 8-device virtual CPU mesh before JAX import.

SURVEY §4's implication for the TPU build: multi-device behavior must be
testable without a TPU. All tests run on 8 virtual CPU devices so DP/FSDP/TP
sharding paths execute real collectives.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# 8 virtual device threads share the host's cores with whatever else runs:
# XLA's CPU collective rendezvous hard-aborts the whole process
# (rendezvous.cc Check failure -> SIGABRT) if any participant thread is
# starved past the default 40 s. Raise the termination timeout so slow is
# slow, not fatal.
if "collective_call_terminate_timeout" not in flags:
    flags += " --xla_cpu_collective_call_terminate_timeout_seconds=600"
os.environ["XLA_FLAGS"] = flags

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# Tests compile from nothing, as they always have: the entry points under
# test place a persistent compile cache inside the checkout
# (trlx_tpu/utils/compile_cache.py), and neither its write cost nor a warm
# hit belongs in a tier-1 run.
jax.config.update("jax_enable_compilation_cache", False)


# ---------------------------------------------------------------------- #
# Suite-order isolation: reset module-global parallel context per test.
#
# gpt2_moe's ep mesh is process state installed by MoE trainers at
# construction and read at *trace* time; without a reset, a jit traced in
# a later test (e.g. the hydra/moe-parallel golden tests) can silently
# pick up a stale mesh from whichever MoE e2e ran before it — the classic
# "fails in full-suite order, passes in isolation" leak (ROADMAP Open
# items). Function-scoped: trainers trace their programs inside the test
# that builds them, so clearing *after* each test never breaks a live
# trainer, only cross-test leakage.
# ---------------------------------------------------------------------- #

import pytest  # noqa: E402


# ---------------------------------------------------------------------- #
# The model families' shared tests (tests/family_harness.py): a family's
# test file names its ``FAMILY`` record and imports the contract tests it is
# held to; their cases are that record's.
# ---------------------------------------------------------------------- #

pytest.register_assert_rewrite("family_harness")


@pytest.fixture
def family(request):
    return request.module.FAMILY


def pytest_generate_tests(metafunc):
    for argument, field in (("refusals", "refusals"), ("engine_case", "engine_cases")):
        if argument in metafunc.fixturenames:
            cases = getattr(metafunc.module.FAMILY, field)
            metafunc.parametrize(argument, list(cases.values()), ids=list(cases))


@pytest.fixture(autouse=True)
def _reset_global_parallel_context():
    yield
    import sys as _sys

    moe_mod = _sys.modules.get("trlx_tpu.models.gpt2_moe")
    if moe_mod is not None:  # only if the test actually imported it
        moe_mod.reset()


@pytest.fixture(scope="session", autouse=True)
def _session_in_tmp_dir(tmp_path_factory):
    """The working directory outside any one test (module- and
    session-scoped fixtures that train are set up before the function-scoped
    fixture below): a temporary directory of this session's own — under
    xdist, of this worker's own."""
    os.chdir(tmp_path_factory.mktemp("cwd"))


@pytest.fixture(autouse=True)
def _run_in_tmp_dir(tmp_path, monkeypatch):
    """Every test runs with its own ``tmp_path`` as the working directory.

    What the program defaults into the working directory —
    ``train.checkpoint_dir`` (``"ckpts"``), the health monitor's
    ``health_dumps/``, ``RUN_LEDGER.jsonl`` — then lands there and not in
    the checkout, where 14 test files training with the default
    ``checkpoint_dir`` used to collide under ``-n 6 --dist loadfile``. No
    test may rely on a relative path into the repository: imports come
    from ``sys.path`` (set above) and subprocesses pass ``cwd=REPO``.
    """
    monkeypatch.chdir(tmp_path)

"""``tools/trace_timeline.py::idle_by_span`` on hand-made intervals (ns):
what the device's side of the starved ledger is read with (ISSUE 43)."""

import importlib.util
import os

import pytest

TOOL = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools", "trace_timeline.py")


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location("trace_timeline", TOOL)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# two programs, 100-400 and 600-900, the first with a stall of its own
BUSY = [(100, 200), (250, 400), (600, 900)]
MODULES = [(100, 400), (600, 900)]
WINDOW = (0, 1000)


def test_a_gap_inside_a_module_is_the_devices_own(tool):
    got = tool.idle_by_span(BUSY, MODULES, WINDOW, [(0, 1000, "serve/step")])
    assert got["idle"] == 100 + 50 + 200 + 100
    assert got["in_module"] == 50
    assert got["between"] == {"serve/step": 400}


def test_a_gap_that_straddles_two_spans_is_split_by_overlap(tool):
    """400-600 lies under ``engine/route`` to 450 and ``serve/land`` from
    520: each gets its overlap, their parent what neither covers; a
    midpoint label would give all 200 to the parent."""
    spans = [
        (50, 950, "serve/step"), (380, 450, "engine/route"), (520, 640, "serve/land"),
        (60, 90, "serve/schedule"),
    ]
    got = tool.idle_by_span(BUSY, MODULES, WINDOW, spans)
    assert got["between"] == {
        "engine/route": 50, "serve/land": 80, "serve/schedule": 30,
        "serve/step": 70 + 20 + 50,  # 450-520; 50-60 and 90-100; 900-950
        "caller": 50 + 50,  # 0-50 and 950-1000: outside every span
    }
    assert sum(got["between"].values()) + got["in_module"] == got["idle"]


def test_a_piece_under_no_span_is_the_callers(tool):
    got = tool.idle_by_span(BUSY, MODULES, WINDOW, [])
    assert got["between"] == {"caller": 400} and got["in_module"] == 50
    # a chip that never idles between programs has nothing to split
    assert tool.idle_by_span([(0, 1000)], [(0, 1000)], WINDOW, [])["between"] == {}


def test_launch_and_tail_show_the_skew_and_their_sum_does_not_move_with_it(tool):
    """Two steps of 1000 ns, each launched 100 ns into a dispatch span of
    300 and fetched 50 ns after its end; then the same with the device's
    clock 400 ns early: a step now starts before its own dispatch, and the
    sum of launch and tail is what it was."""
    spans = [(0, 300, "engine/dispatch"), (310, 1150, "engine/fetch"), (1160, 1200, "engine/fetch"),
             (2000, 2300, "engine/dispatch"), (2310, 3150, "engine/fetch")]
    steps = [(100, 1100, "jit_decode_step(1)"), (2100, 3100, "jit_decode_step(1)")]
    true = tool.launch_and_tail(steps, spans)
    assert true["steps"] == 2 and true["launch_us"] == [0.1] * 4 and true["tail_us"] == [0.05] * 4
    assert true["dispatch_call_us"] == [0.3] * 4
    early = tool.launch_and_tail([(s - 400, e - 400, n) for s, e, n in steps], spans)
    assert early["launch_us"] == [-0.3] * 4 and early["tail_us"] == [0.45] * 4
    assert early["launch_plus_tail_us"] == true["launch_plus_tail_us"] == [pytest.approx(0.15)] * 4
    assert tool.launch_and_tail(steps, []) == {}

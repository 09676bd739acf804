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


def test_a_gap_under_a_collection_inside_a_dispatch_goes_to_the_collection(tool):
    """A full collection (``host/gc``, ISSUE 60) that fell inside the call
    into the step, 420-580 of ``engine/dispatch`` 410-590: the idle under
    it is the collector's, what is left of the call's the call's."""
    spans = [(50, 950, "serve/step"), (410, 590, "engine/dispatch"), (420, 580, "host/gc")]
    got = tool.idle_by_span(BUSY, MODULES, WINDOW, spans)
    assert got["between"] == {
        "host/gc": 160, "engine/dispatch": 10 + 10,
        "serve/step": 10 + 10 + 50 + 50,  # 400-410, 590-600; 50-100, 900-950
        "caller": 50 + 50,
    }


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


def test_a_step_in_flight_is_paired_with_the_fetch_of_its_own_outputs(tool):
    """The loop one step behind (ISSUE 44): step n's dispatch is entered
    100 ns after step n-2 ended (its fetch returned 50 ns after), so
    while n-1 runs, and its outputs are fetched an iteration later,
    behind the dispatch of n+1, returning 50 ns after step n ends. Steps
    follow one another 20 ns apart, but for one pair with a 300 ns
    admission forward between them, during which the dispatch of the step
    after next is entered. Each step is paired with its own dispatch and
    its own fetch, with the device's clock 400 ns early too."""
    steps, spans = [], []
    at = 100
    for n in range(6):
        steps.append((at, at + 980, "jit_decode_step(1)"))
        at += 1000 + (300 if n == 2 else 0)
    forward = [(steps[2][1] + 10, steps[2][1] + 310, "jit_prefill_chunk(2)")]
    spans.append((0, 90, "engine/dispatch"))  # step 0: from an empty pipeline
    for n in range(1, 6):
        d = steps[n - 2][1] + 100 if n > 1 else steps[0][0] + 200
        spans.append((d, d + 100, "engine/dispatch"))  # step n, behind n-1
        spans.append((d + 110, steps[n - 1][1] + 50, "engine/fetch"))  # n-1's tokens
        spans.append((steps[n - 1][1] + 60, steps[n - 1][1] + 70, "engine/fetch"))  # n-1's flags
    spans.append((steps[5][0] + 200, steps[5][1] + 50, "engine/fetch"))  # the tail read out
    got = tool.launch_and_tail(steps, spans, forward)
    assert got["steps"] == 6 and got["ahead_share"] == 1.0
    assert got["tail_us"] == [0.05] * 4 and got["clock_shift_us"] == 0.05
    # entered 80 ns into the step before: it waited out the other 900,
    # the gap, and twice the forward as well (the step behind it, and the
    # next, whose dispatch was entered while the forward ran)
    assert got["launch_us"] == [0.1, 0.92, 1.22, 1.22]
    assert got["device_gap_us"] == [0.02] * 4  # the forward's 300 ns are not idle
    early = tool.launch_and_tail(
        [(s - 400, e - 400, n) for s, e, n in steps], spans,
        [(s - 400, e - 400, n) for s, e, n in forward],
    )
    assert early["steps"] == 6 and early["ahead_share"] == 1.0
    assert early["tail_us"] == [0.45] * 4 and early["clock_shift_us"] == 0.45
    assert early["launch_plus_tail_us"] == [pytest.approx(v) for v in got["launch_plus_tail_us"]]
    assert early["device_gap_us"] == got["device_gap_us"]
    # the order every step had before: read out behind its own dispatch
    true = tool.launch_and_tail(
        [(100, 1100, "jit_decode_step(1)"), (2100, 3100, "jit_decode_step(1)")],
        [(0, 300, "engine/dispatch"), (310, 1150, "engine/fetch"), (1160, 1200, "engine/fetch"),
         (2000, 2300, "engine/dispatch"), (2310, 3150, "engine/fetch")],
    )
    assert true["ahead_share"] == 0.0 and true["device_gap_us"] == [1.0] * 4

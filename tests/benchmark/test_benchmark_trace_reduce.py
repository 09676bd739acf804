"""The trace reduction on the small recorded trace kept with the benchmark
(``benchmark/testdata/small.xplane.txt`` is its readable form). By hand,
device 0, in ns:

    XLA Ops        fusion.1 0-30000 | while.1 30000-60000 { fusion.2 30000-45000,
                   all-reduce.1 45000-55000, fusion.3 55000-60000 } | idle
                   60000-70000 | copy.4 70000-90000 | all-gather-done.1
                   90000-92000 | fusion.5 92000-100000
    Async XLA Ops  all-gather-start.1 72000-92000
    XLA Modules    jit_train_step 0-60000, jit_sampler 70000-100000
    host           bench/phase 0-100000, bench/collect 58000-75000

so busy 90000 of 100000; collectives cover 45000-55000 and 72000-92000 =
30000, of which copy.4 hides 72000-90000: exposed 12000. Device 1 is busy
0-50000 and 80000-100000 = 70000. Mean busy 80000: idle share 20%.
"""

import os
import types

import pytest

from benchmark import harness, readers, trace_reduce as tr

PB = os.path.join(harness.HERE, "testdata", "small.xplane.pb")
TXT = os.path.join(harness.HERE, "testdata", "small.xplane.txt")


def test_recorded_trace_is_its_readable_form():
    from jax.profiler import ProfileData

    def events(data):
        return sorted(
            (p.name, l.name, e.name, int(e.start_ns), int(e.duration_ns))
            for p in data.planes for l in p.lines for e in l.events
        )

    with open(TXT) as f:
        text = ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(f.read()))
    recorded = events(ProfileData.from_file(PB))
    assert recorded == events(text) and len(recorded) == 17


def test_interval_arithmetic():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == [(0, 2), (4, 8), (22, 29)]
    assert tr.length([(0, 4), (5, 9)]) == 8
    events = [(0, 10, "outer"), (2, 5, "a"), (5, 9, "a"), (12, 15, "b")]
    assert tr.self_times(events) == {"outer": 3, "a": 7, "b": 3}
    assert [e[2] for e in tr.leaves(events)] == ["a", "a", "b"]


def test_instruction_names_are_cut_to_kind_and_shape():
    assert tr.op_kind("%copy.743 = bf16[64,560,16,64]{3,2,0,1:T(8,128)(2,1)} copy(bf16[64] %x)") == "copy bf16[64,560,16,64]"
    assert tr.op_kind("%multiply_add_fusion.13 = (f32[5,4]{1,0}, f32[5,4]{1,0}) fusion(") == "multiply_add_fusion f32[5,4]"
    assert tr.op_kind("%all-gather-start.3 = (bf16[8,128]{1,0}, bf16[32,128]) all-gather-start(") == "all-gather-start bf16[8,128]"
    assert tr.module_name("jit_train_phase(2956675360083004719)") == "jit_train_phase"


def test_device_zero_exactly():
    devices, host = tr.read_planes(PB)
    assert sorted(devices) == [0, 1]
    d0 = tr.reduce_device(devices[0]["ops"], devices[0]["modules"], devices[0]["async"])
    assert d0["busy"] == [(0, 60000), (70000, 100000)] and d0["busy_ns"] == 90000
    assert d0["modules"] == {"jit_train_step": {"ns": 60000, "count": 1},
                             "jit_sampler": {"ns": 30000, "count": 1}}
    assert d0["collective_ns"] == 30000 and d0["collective_exposed_ns"] == 12000
    assert d0["op_self_ns"] == {
        "fusion f32[8,8]": 30000, "while s32[]": 0, "fusion f32[4,8]": 20000,
        "all-reduce f32[8]": 10000, "copy bf16[4,4]": 20000,
        "all-gather-done bf16[8,4]": 2000, "fusion f32[2,2]": 8000,
    }
    assert d0["op_count"] == {
        "fusion f32[8,8]": 1, "while s32[]": 1, "fusion f32[4,8]": 2, "all-reduce f32[8]": 1,
        "copy bf16[4,4]": 1, "all-gather-done bf16[8,4]": 1, "fusion f32[2,2]": 1,
    }
    assert tr.label_gaps(d0["busy"], (0, 100000), host) == {"collect": 10000}
    assert tr.label_gaps(d0["busy"], (0, 100000), []) == {"unlabelled": 10000}
    d1 = tr.reduce_device(devices[1]["ops"], devices[1]["modules"])
    assert d1["busy_ns"] == 70000 and d1["collective_ns"] == 0


def test_whole_trace_and_the_readers_built_on_it():
    out = tr.reduce_trace(PB)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(80000e-9, rel=1e-12)
    assert out["span_s"] == pytest.approx(100000e-9, rel=1e-12)
    assert 1 - out["busy_s"] / out["span_s"] == pytest.approx(0.20, rel=1e-9)
    assert out["modules"]["jit_train_step"] == {"s": pytest.approx(6e-5), "count": 1}
    assert out["device_ops"][0] == ["fusion f32[8,8]", pytest.approx(3e-5)]
    assert all(s > 0 for _, s in out["device_ops"]) and len(out["device_ops"]) == 6
    # every kind's time and count is kept for the readers, a container's too
    assert len(out["ops"]) == 7 and out["ops"]["while s32[]"] == {"s": 0.0, "count": 1}
    assert out["ops"]["fusion f32[4,8]"] == {"s": pytest.approx(2e-5), "count": 2}
    assert out["idle_gaps"] == [["collect", pytest.approx(1e-5)]]
    assert out["collective_exposed_s"] / out["collective_s"] == pytest.approx(0.4, rel=1e-9)

    record = {"xplane": PB, "phases": 2, "chips": 1, "flops": (0.0, 197e12 * 3e-5),
              "device": {"peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}}
    # the train module took 6e-5 s over 2 phases; 3e-5 s of peak work a phase
    spec = {"kind": "module_roofline", "module": "^jit_train_(step|phase)$", "flops": "train"}
    assert readers.module_roofline(record, spec) == pytest.approx(100.0, rel=1e-9)
    assert readers.collective(record, {"what": "ms_per_phase"}) == pytest.approx(3e-5 * 1e3 / 2)
    assert readers.collective(record, {"what": "exposed_share"}) == pytest.approx(40.0)
    assert readers.module_roofline(record, dict(spec, module="^jit_absent")) is None


def test_one_kernels_share_of_its_roofline_from_the_recorded_trace():
    """``fusion f32[4,8]`` ran twice on device 0 (fusion.2 15000 ns, fusion.3
    5000 ns): 2e-5 s. Say one execution needs 0.985e9 FLOPs and 4.095e6
    bytes: two need 1.97e9 / 197e12 = 1e-5 s of the matrix unit and 8.19e6 /
    819e9 = 1e-5 s of the memory, so the kernel is at 50% of either roof.
    With 3x the bytes the memory bounds it: 3e-5 s needed, 150%."""
    calls = []

    def cost(record, ops):
        calls.append(ops)
        n = sum(op["count"] for op in ops.values())
        return n * 0.985e9, n * 4.095e6 * record["bytes_scale"]

    record = {"xplane": PB, "bytes_scale": 1, "cell": {"family": types.SimpleNamespace(kernel_cost=cost)},
              "device": {"peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}}
    spec = {"kind": "op_roofline", "op": r"^fusion f32\[4,8\]$", "count": "kernel_cost"}
    assert readers.READERS["op_roofline"](record, spec) == pytest.approx(50.0, rel=1e-9)
    assert calls[-1] == {"fusion f32[4,8]": {"s": pytest.approx(2e-5), "count": 2}}
    assert readers.op_roofline(dict(record, bytes_scale=3), spec) == pytest.approx(150.0, rel=1e-9)
    # every fusion: 30000 + 20000 + 8000 ns, four executions
    every = readers.op_roofline(record, dict(spec, op="^fusion "))
    assert every == pytest.approx(100.0 * 4 * 0.985e9 / 197e12 / 5.8e-5, rel=1e-9)
    # an operation the trace does not hold, or no trace: nothing
    assert readers.op_roofline(record, dict(spec, op="^custom-call")) is None
    assert readers.op_roofline(dict(record, xplane=None), spec) is None
    # a count function the family lacks is looked for in arithmetic.py, and is an error there
    with pytest.raises(AttributeError):
        readers.op_roofline(record, dict(spec, count="no_such_function"))


def test_a_counter_or_gauge_of_the_registry_by_name():
    record = {"counters": {"moe/tokens_routed": 1200.0, "attention/decode_path{path=fused}": 48.0},
              "gauges": {"engine/occupancy": 25.5}, "phases": 3, "window_s": 8.0}
    read = readers.READERS["counter"]
    assert read(record, {"name": "moe/tokens_routed"}) == 1200.0
    assert read(record, {"name": "moe/tokens_routed", "per": "phase"}) == 400.0
    assert read(record, {"name": "moe/tokens_routed", "per": "second"}) == 150.0
    assert read(record, {"name": "attention/decode_path{path=fused}", "per": "phase"}) == 16.0
    assert read(record, {"name": "engine/occupancy"}) == 25.5
    # a name the program did not write, or a record without the registry: nothing
    assert read(record, {"name": "moe/absent"}) is None
    assert read({"phases": 1}, {"name": "moe/tokens_routed", "per": "second"}) is None
    specs = [{"name": "routed", "unit": "tokens", "reader": {"kind": "counter", "name": "moe/absent"}}]
    assert readers.read_all(record, specs) == {}


def test_registry_scalars_leave_a_windows_own_increments():
    from trlx_tpu import telemetry

    with telemetry.scoped_metrics(telemetry.MetricsRegistry()) as registry:
        registry.counter("moe/tokens_routed").inc(5)
        registry.gauge("engine/occupancy").set(3.0)
        before = harness.registry_scalars()
        registry.counter("moe/tokens_routed").inc(7)
        registry.counter("moe/dropped").inc(2)
        registry.gauge("engine/occupancy").set(4.0)
        registry.histogram("serve/e2e_ms").observe(1.0)
        assert harness.registry_scalars(before) == {
            "counters": {"moe/tokens_routed": 7.0, "moe/dropped": 2.0},
            "gauges": {"engine/occupancy": 4.0}}


def test_clip_cuts_events_to_a_window():
    events = [(0, 10, "before"), (8, 14, "across"), (15, 18, "inside"), (19, 30, "over"), (30, 40, "after")]
    assert tr.clip(events, (10, 20)) == [(10, 14, "across"), (15, 18, "inside"), (19, 20, "over")]


def test_trace_cut_to_a_host_span_exactly():
    """Cut to ``bench/collect`` (58000-75000): device 0 keeps the end of
    while.1 / fusion.3 (58000-60000) and the start of copy.4 (70000-75000),
    busy 7000 of 17000; device 1 is idle throughout; mean busy 3500."""
    out = tr.reduce_trace(PB, clip_span="collect")
    assert out["busy_s"] == pytest.approx(3.5e-6, rel=1e-12)
    assert out["span_s"] == pytest.approx(1.7e-5, rel=1e-12)
    assert out["modules"] == {"jit_train_step": {"s": 2e-6, "count": 1},
                              "jit_sampler": {"s": 5e-6, "count": 1}}
    assert out["collective_s"] == pytest.approx(3e-6)  # all-gather-start 72000-75000, under copy.4
    assert out["collective_exposed_s"] == 0.0
    # a span the trace does not hold cuts nothing
    assert tr.reduce_trace(PB, clip_span="absent") == tr.reduce_trace(PB)

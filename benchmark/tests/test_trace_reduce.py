"""``trace_reduce.py``'s reductions on a trace small enough to work out
by hand, and on one small trace recorded on the chip.

The hand-made trace, one device, seconds:

    program A (a step):   conv    0.0 - 4.0   [convolution fusion]
                          add     4.0 - 5.0   [loop fusion]
                          all-red 4.5 - 7.0   [all-reduce], 4.5-5.0 under add
    idle                  7.0 - 8.0            (host span ``input_wait``)
    program B (a step):   conv    8.0 - 9.0   [convolution]
    idle                  9.0 - 10.0           (no host span)
"""

import glob
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import trace_reduce as tr  # noqa: E402
from trace_reduce import Op, Span  # noqa: E402

A, B = (0, "step"), (1, "step")
OPS = [
    Op("fusion.1", "convolution fusion", 0.0, 4.0, A),
    Op("add.2", "loop fusion", 4.0, 5.0, A),
    Op("all-reduce.3", "all-reduce", 4.5, 7.0, A),
    Op("convolution.4", "convolution", 8.0, 9.0, B),
]
HOST = [Span("input_wait", 6.9, 8.0), Span("dispatch", 8.0, 8.1)]


def test_busy_and_idle_share():
    assert tr.busy_seconds(OPS, 0.0, 10.0) == pytest.approx(8.0)
    # a window that cuts the first and the last operation
    assert tr.busy_seconds(OPS, 2.0, 8.5) == pytest.approx(5.5)


def test_convolution_seconds():
    assert tr.category_seconds(OPS, tr.is_conv) == pytest.approx(5.0)


def test_a_kernel_in_a_convolutions_place_is_counted_with_them():
    """Program A with its convolution's second half (2.0 - 4.0) moved to a
    Pallas kernel: the time that carries convolutions is still 4.0 s, so a
    share of the roofline whose least time is of all the convolutions
    does not rise because some of them left XLA."""
    text = ('%custom-call.7 = bf16[3,3,128,128]{3,2,1,0} custom-call('
            'bf16[16,320,480,128]{3,2,1,0} %x, bf16[16,320,480,128]{3,2,1,0} '
            '%g), custom_call_target="tpu_custom_call", operand_layout_'
            'constraints={}')
    name, category = tr.parse_op(text)
    assert (name, category) == ("custom-call.7 bf16[3,3,128,128]", "custom-call")
    moved = [Op("fusion.1", "convolution fusion", 0.0, 2.0, A),
             Op(name, category, 2.0, 4.0, A)] + OPS[1:3]
    assert tr.category_seconds(moved, tr.is_conv) == pytest.approx(4.0)
    assert tr.category_seconds(moved, tr.is_conv) == tr.category_seconds(
        OPS[:3], tr.is_conv)


def test_exposed_collective_seconds():
    # 4.5-7.0 in the all-reduce, of which 4.5-5.0 runs under the add
    assert tr.exposed_seconds(OPS, tr.is_collective) == pytest.approx(2.0)
    assert tr.exposed_seconds(OPS[:2], tr.is_collective) == 0.0
    # an asynchronous pair is in flight from its start to its done:
    # 1.0-3.0, of which 1.5-2.5 runs under the convolution
    pair = [Op("all-reduce-start.1", "all-reduce-start", 1.0, 1.1, A),
            Op("fusion.1", "convolution fusion", 1.5, 2.5, A),
            Op("all-reduce-done.1", "all-reduce-done", 2.9, 3.0, A)]
    assert tr.exposed_seconds(pair, tr.is_collective) == pytest.approx(1.0)


def test_step_programs_and_their_span():
    steps = tr.step_programs(OPS, min_ops=1)
    assert [len(s) for s in steps] == [3, 1]
    spans = [max(o.end for o in s) - min(o.start for o in s) for s in steps]
    assert spans == [pytest.approx(7.0), pytest.approx(1.0)]
    assert tr.median(spans) == pytest.approx(4.0)
    assert tr.step_programs(OPS, min_ops=2) == [steps[0]]


def test_the_traced_window_and_the_readers_on_it(monkeypatch):
    """A run as the driver hands it to the readers: the host's clock is
    100 s ahead of the profiler's, the traced part is 0 - 9.5 s of it."""
    import functools
    import importlib.util

    # the hand-made step programs have three operations and one
    monkeypatch.setattr(tr, "step_programs",
                        functools.partial(tr.step_programs, min_ops=1))

    def reader(name):
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "layer_metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    run = {"trace": {"devices": {0: OPS}, "host": HOST,
                     "sync": (1.0, 101.0e9)},
           "window": {"t0": 100.0, "traced": (100.0, 109.5), "steps": 4,
                      "images": 8, "seconds": 19.5, "wait_s": 3.0,
                      "untraced": {"t0": 110.0, "steps": 2, "images": 4,
                                   "seconds": 10.0, "wait_s": 0.5}},
           # one h2d span in the traced part, two in the rest
           "spans": [{"phase": "h2d", "t0": 101.0, "t1": 103.0},
                     {"phase": "h2d", "t0": 111.0, "t1": 111.5},
                     {"phase": "stack", "t0": 112.0, "t1": 113.0},
                     {"phase": "h2d", "t0": 115.0, "t1": 115.5}],
           "rehearsal": False, "chips": 1, "train_flops_per_image": 5.0,
           "peak": {"bf16_flops": 100.0}}
    assert tr.traced_window(run) == pytest.approx((0.0, 9.5))
    # both step programs lie wholly inside: 7.0 and 1.0 s, median 4.0
    assert tr.median_step_seconds(run) == pytest.approx(4.0)
    assert reader("device_step_ms")(run) == pytest.approx(4000.0)
    assert reader("device_idle_pct")(run) == pytest.approx(100 * 1.5 / 9.5)
    # the host-clock metrics are of the untraced rest alone: 2 steps, 4
    # images, 0.5 s waited and 1.0 s of h2d in 10 s
    assert reader("input_wait_ms")(run) == pytest.approx(250.0)
    assert reader("h2d_ms")(run) == pytest.approx(500.0)
    assert reader("step_mfu")(run) == pytest.approx(100 * 5.0 * 4 / 10.0 / 100.0)
    assert reader("allreduce_exposed_ms")(run) == pytest.approx(1000.0)
    # a window that cuts the first program leaves one whole step
    run["window"]["traced"] = (100.5, 109.5)
    assert tr.median_step_seconds(run) == pytest.approx(1.0)
    run["trace"]["sync"] = None
    assert tr.median_step_seconds(run) is None
    assert reader("device_step_ms")(run) is None
    # a traced run whose window ended with the trace has no rest to read
    run["window"]["untraced"] = None
    assert reader("input_wait_ms")(run) is None
    assert reader("h2d_ms")(run) is None
    assert reader("step_mfu")(run) is None


def test_idle_gaps_are_labelled_by_the_host_span_open():
    gaps = tr.idle_gaps(OPS, HOST, 0.0, 10.0)
    assert gaps == [["input_wait", pytest.approx(1.0)],
                    ["host_other", pytest.approx(1.0)]]


def test_top_ops_give_categories_then_operations():
    ops = OPS + [Op("fusion.1", "convolution fusion", 9.0, 9.5, B)]
    assert tr.top_ops(ops, n=3, categories=1) == [
        ["all convolution fusion", pytest.approx(4.5)],
        ["fusion.1 [convolution fusion]", pytest.approx(4.5)],
        ["all-reduce.3 [all-reduce]", pytest.approx(2.5)]]


def test_parse_op_reads_opcode_fusion_kind_and_shape():
    text = ("%fusion.19 = bf16[3,3,512,256]{3,2,1,0:T(8,128)(2,1)S(1)} "
            "fusion(bf16[16,160,240,256]{3,0,2,1:T(8,128)(2,1)} %gte.1, "
            "bf16[16,160,240,256]{3,0,2,1} %convolution_add_fusion.5), "
            "kind=kOutput, calls=%fused_computation")
    assert tr.parse_op(text) == ("fusion.19 bf16[3,3,512,256]",
                                 "convolution fusion")
    assert tr.parse_op("%copy.2 = bf16[2,64]{1,0} copy(bf16[2,64]{0,1} %a)") == (
        "copy.2 bf16[2,64]", "copy")
    assert tr.parse_op("%all-reduce.1 = f32[32]{0} all-reduce(f32[32]{0} %g), "
                       "replica_groups={}")[1] == "all-reduce"
    assert tr.parse_op("%f.2 = (f32[8]{0}, f32[8]{0}) fusion(f32[8]{0} %x), "
                       "kind=kLoop, calls=%c")[1] == "loop fusion"


def test_attach_programs():
    bare = [o._replace(program=None) for o in OPS]
    modules = [(0.0, 7.0, "step"), (8.0, 9.0, "step")]
    assert [o.program for o in tr.attach_programs(bare, modules)] == [
        A, A, A, B]


RECORDED = sorted(glob.glob(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data", "*.xplane.pb")))


@pytest.mark.skipif(not RECORDED, reason="no recorded trace under tests/data")
def test_recorded_trace_reads_as_by_hand():
    """The small trace recorded on the chip (three runs of one jitted
    convolution, ReLU and sum): the loader's numbers against a second,
    plain walk of the same file."""
    import jax

    loaded = tr.load(RECORDED[0])
    assert list(loaded["devices"]) == [0]
    ops = loaded["devices"][0]
    data = jax.profiler.ProfileData.from_file(RECORDED[0])
    plane = next(p for p in data.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    events = sorted((e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
    assert len(ops) == len(events)
    # the operations of one program run do not overlap here, so busy time
    # is the plain sum of the durations
    by_hand = sum(e - s for s, e in events) * 1e-9
    t0, t1 = events[0][0] * 1e-9, events[-1][1] * 1e-9
    assert tr.busy_seconds(ops, t0, t1) == pytest.approx(by_hand, rel=1e-6)
    steps = tr.step_programs(ops, min_ops=1)
    assert len(steps) == 3
    assert tr.category_seconds(ops, tr.is_conv) > 0
    assert [s.name for s in loaded["host"]].count("dispatch") == 3

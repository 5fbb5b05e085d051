"""The seven readers of the feed's spans on a run small enough to work
out by hand.

The hand-made run: the host's clock is 100 s ahead of the profiler's; the
traced part of the window is 100.0 - 109.5 s of it, the untraced rest
begins at 110.0 s and holds 4 steps: the end of epoch 3 (its batch 5) and
the first three batches of epoch 4. Seconds, host clock:

    epoch 3   fetch 5        110.0 - 110.3      epoch 4   fetch 0        111.0 - 111.4
              h2d 5          110.3 - 110.4                h2d 0          111.4 - 111.5
              h2d_ready 5    110.4 - 110.6                h2d_ready 0    111.5 - 111.9
              feed_wait 5    110.1 - 110.4                feed_wait 0    111.0 - 111.5
              fetch end      110.4 - 110.5                fetch 1        111.5 - 111.8
              feed_wait end  110.9 - 111.0                stack 1        111.8 - 111.9
                                                          h2d 1          111.9 - 112.0
                                                          feed_wait 1    111.7 - 112.0
                                                          slot_wait 2    112.3 - 112.8
                                                          feed_wait 2    112.2 - 112.3
                                                          feed_wait 3    112.9 - 113.0

and in the traced part (device 0's operations are ``test_trace_reduce``'s:
busy 0 - 7 and 8 - 9 on the profiler's clock, idle 7 - 8 and 9 - 9.5):

    epoch 2   feed_wait 0    106.5 - 107.5   (0.5 s of it idle: 7.0 - 7.5)
              feed_wait 1    109.2 - 109.9   (0.3 s of it idle and traced: 9.2 - 9.5)
              fetch 0        106.5 - 107.4
"""

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from test_trace_reduce import HOST, OPS  # noqa: E402

NAMES = ("feed_batch_ms", "feed_fetch_ms", "feed_slot_wait_ms", "feed_wait_ms",
         "epoch_start_wait_ms", "h2d_ready_ms", "idle_in_feed_wait_pct")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(phase, t0, t1, epoch, seq=None, **tags):
    if seq is not None:
        tags["seq"] = seq
    return {"phase": phase, "t0": t0, "t1": t1, "epoch": epoch, **tags}


SPANS = [
    span("feed_wait", 106.5, 107.5, 2, 0), span("fetch", 106.5, 107.4, 2, 0),
    span("feed_wait", 109.2, 109.9, 2, 1),
    span("fetch", 110.0, 110.3, 3, 5), span("h2d", 110.3, 110.4, 3, 5),
    span("h2d_ready", 110.4, 110.6, 3, 5), span("feed_wait", 110.1, 110.4, 3, 5),
    span("fetch", 110.4, 110.5, 3, 6, end=True),
    span("feed_wait", 110.9, 111.0, 3, 6, end=True),
    span("fetch", 111.0, 111.4, 4, 0), span("h2d", 111.4, 111.5, 4, 0),
    span("h2d_ready", 111.5, 111.9, 4, 0), span("feed_wait", 111.0, 111.5, 4, 0),
    span("fetch", 111.5, 111.8, 4, 1), span("stack", 111.8, 111.9, 4, 1),
    span("h2d", 111.9, 112.0, 4, 1), span("feed_wait", 111.7, 112.0, 4, 1),
    span("slot_wait", 112.3, 112.8, 4, 2), span("feed_wait", 112.2, 112.3, 4, 2),
    span("feed_wait", 112.9, 113.0, 4, 3),
    {"phase": "dispatch", "t0": 111.5, "t1": 111.6, "step": 9},
]


def hand_made_run(spans=SPANS):
    return {"trace": {"devices": {0: OPS}, "host": HOST, "sync": (1.0, 101.0e9)},
            "window": {"t0": 100.0, "traced": (100.0, 109.5), "steps": 10,
                       "untraced": {"t0": 110.0, "steps": 4, "images": 8,
                                    "seconds": 4.0, "wait_s": 1.4}},
            "spans": list(spans)}


BY_HAND = {
    # fetch 0.3 + 0.1 + 0.4 + 0.3, stack 0.1, h2d 0.1 x 3: 1.5 s of 4 steps
    "feed_batch_ms": 375.0,
    "feed_fetch_ms": 275.0,
    "feed_slot_wait_ms": 125.0,
    # 0.3 + 0.1 (the end) + 0.5 + 0.3 + 0.1 + 0.1
    "feed_wait_ms": 350.0,
    # one epoch began in the rest (4): its waits of seq 0, 1, 2
    "epoch_start_wait_ms": 900.0,
    "h2d_ready_ms": 150.0,
    # idle 7 - 8 and 9 - 9.5 on the profiler's clock; the loop waited
    # 6.5 - 7.5 and 9.2 - 9.9 of it: 0.5 + 0.3 of 1.5 s
    "idle_in_feed_wait_pct": 100 * 0.8 / 1.5,
}


@pytest.mark.parametrize("name", NAMES)
def test_each_reader_on_the_hand_made_run(name):
    assert reader(name)(hand_made_run()) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_spans_gives_nothing_to_read(name):
    """The parent's spans are ``h2d`` and ``dispatch`` alone: not 0, nothing."""
    old = [s for s in SPANS if s["phase"] in ("h2d", "dispatch")]
    assert reader(name)(hand_made_run(old)) is None


@pytest.mark.parametrize("name", NAMES[:6])
def test_a_run_without_an_untraced_rest_gives_nothing_to_read(name):
    run = hand_made_run()
    run["window"]["untraced"] = None
    assert reader(name)(run) is None
    run["window"]["untraced"] = {"t0": 110.0, "steps": 0, "images": 0,
                                 "seconds": 0.1, "wait_s": 0.0}
    assert reader(name)(run) is None


def test_a_worker_that_never_waited_reads_no_slot_wait_not_nothing():
    run = hand_made_run([s for s in SPANS if s["phase"] != "slot_wait"])
    assert reader("feed_slot_wait_ms")(run) == 0.0
    # and the span it is the reader of: dropped by the program, not read
    run = hand_made_run([s for s in SPANS if s["phase"] != "h2d_ready"])
    assert reader("h2d_ready_ms")(run) is None
    assert reader("feed_batch_ms")(run) == pytest.approx(375.0)


def test_epoch_start_takes_whole_epochs_begun_in_the_rest():
    """Epoch 3's last wait lies in the rest, its first does not: not an
    epoch's start. A second epoch begun there halves nothing: the mean."""
    more = SPANS + [span("feed_wait", 113.5, 113.7, 5, 0),
                    span("feed_wait", 113.9, 114.0, 5, 1)]
    assert reader("epoch_start_wait_ms")(hand_made_run(more)) == pytest.approx(
        1e3 * (0.9 + 0.3) / 2)
    late = [s for s in SPANS if not (s["phase"] == "feed_wait"
                                     and s["epoch"] == 4 and s["seq"] == 0)]
    assert reader("epoch_start_wait_ms")(hand_made_run(late)) is None


def test_idle_share_needs_a_device_trace_and_idle_time():
    run = hand_made_run()
    run["trace"]["sync"] = None
    assert reader("idle_in_feed_wait_pct")(run) is None
    run = hand_made_run()
    run["trace"] = None
    assert reader("idle_in_feed_wait_pct")(run) is None
    # a traced window in which the device never idles: 0 - 7 s
    run = hand_made_run()
    run["window"]["traced"] = (100.0, 107.0)
    assert reader("idle_in_feed_wait_pct")(run) is None
    # the loop never waited while the device idled
    busy = [s for s in SPANS if s.get("epoch") != 2] + [
        span("feed_wait", 101.0, 102.0, 2, 0)]
    assert reader("idle_in_feed_wait_pct")(hand_made_run(busy)) == 0.0

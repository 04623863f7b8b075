"""The program's clock on the trace's timeline, and the readers of the
round phases and front-door spans (CPU)."""
import glob
import os

import pytest

from bench.harness import Reading, read_metric
from bench.trace import clock, xplane
from bench.trace.xplane import Op, Reduction
from repro.obs.trace import Span, Tracer


def _record(tmp_path):
    """A CPU profile of a few spans and an event, with the tracer enabled
    and profiling on; returns (tracer, trace file)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tr = Tracer()
    tr.enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    tr.profiling = True
    try:
        for call in range(3):
            with tr.span("door.prepare", call=call, method="proposed"):
                y = f(x)
            with tr.span("door.execute", call=call):
                y.block_until_ready()
                tr.event("compile.runner_miss", engine="test")
    finally:
        tr.profiling = False
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    return tr, path


def test_spans_land_on_their_annotations(tmp_path):
    from jax.profiler import ProfileData

    tr, path = _record(tmp_path)
    red = xplane.reduce_file(path)
    off = clock.offset_ns(red, tr.spans)
    assert off is not None
    pd = ProfileData.from_file(path)
    anns, start_time = [], None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start_time = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("door."):
                    anns.append((e.start_ns, e.name, dict(e.stats)))
    anns.sort(key=lambda a: a[0])
    assert len(anns) == len(tr.spans) == 6
    for sp, (start, name, stats) in zip(tr.spans, anns):
        assert name == sp.name
        assert stats["span"] == sp.index                # the span's index
        assert stats["call"] == sp.attrs["call"]        # and its attributes
        t0, t1 = clock.interval(sp, off)
        assert abs(t0 - start) < 50e3                   # within 50 µs
        assert t0 < t1
    # the offset is the trace's own start on the program's clock
    assert start_time is not None
    assert abs(off - start_time) < 1e6                  # within 1 ms
    # an event lands inside the span that was open when it was recorded
    assert len(tr.events) == 3
    for ev in tr.events:
        e0, e1 = clock.interval(ev, off)
        host = clock.interval(tr.spans[ev["parent"]], off)
        assert e0 == e1 and host[0] <= e0 <= host[1]


def test_offset_needs_stamped_spans():
    red = Reduction(ops=[], t0=0, t1=10, n_devices=1,
                    host=[(0, 5, "door.prepare")])

    class Old:                                          # no t0_ns field
        name, index, t0, wall_s = "door.prepare", 0, 1.0, 0.1

    assert clock.offset_ns(red, [Old()]) is None
    assert clock.offset_ns(red, []) is None


def test_shared_ns():
    assert clock.shared_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert clock.shared_ns([(0, 10)], [(2, 4), (3, 6)]) == 4   # union of b
    assert clock.shared_ns([(0, 10)], [(10, 20)]) == 0


OFFSET = 1_000_000_000


def _span(name, index, t0, t1):
    return Span(name=name, t0_ns=t0 + OFFSET, t1_ns=t1 + OFFSET, index=index)


def _reading(traced):
    """One call on a built timeline: the device idle in [100, 200) and
    [400, 1000); host annotations at the spans' trace times."""
    ops = [Op("gather.1", 0, 60, "gather",
              "jit(f)/while/body/closed_call/batch_sample/vmap()/gather", 0),
           Op("fusion.1", 60, 100, "fusion",
              "jit(f)/dp_privatize/vmap(jit(_pad))/dp_layout/reshape", 0),
           Op("scatter.1", 200, 300, "scatter",
              "jit(f)/carry_update/scatter-add", 0),
           Op("fusion.2", 300, 400, "fusion", "jit(f)/selection/add", 0)]
    spans = [_span("sweep.prepare", 0, 90, 150),         # 50 idle
             _span("sweep.execute", 1, 150, 390),        # not a host phase
             _span("sweep.readback", 2, 390, 600),       # 200 idle
             _span("sweep.results", 3, 600, 700),        # 100 idle
             _span("runner.build", 4, 700, 900)]         # not a front door
    host = [(0, 1000, "bench.segment")] + [
        (s.t0_ns - OFFSET, s.t1_ns - OFFSET, s.name) for s in spans]
    red = Reduction(ops=ops, t0=0, t1=1000, n_devices=1, host=host)
    return Reading(cell=None, trace=red, spans=spans, traced=traced,
                   e2e={}, peaks=None, chips=1)


@pytest.mark.parametrize("name, want", [
    ("batch_sample_us.train", 60e-3 / 2),       # 60 ns over 2 lane-rounds
    ("dp_layout_us.train", 40e-3 / 2),
    ("carry_update_us.train", 100e-3 / 2),
    ("front_door_idle_ms.train", 350e-6),       # 50 + 200 + 100 ns, 1 call
])
def test_known_values_on_a_built_timeline(name, want):
    r = _reading({"calls": 1, "lane_rounds": 2})
    assert read_metric(name, r) == pytest.approx(want)


def test_readers_find_nothing_where_the_program_has_no_marker():
    r = _reading({"calls": 1, "lane_rounds": 2})
    r.trace.ops = [o for o in r.trace.ops if "selection" in o.op_name]
    for name in ("batch_sample_us.train", "dp_layout_us.train",
                 "carry_update_us.train"):
        assert read_metric(name, r) is None
    r.spans = [s for s in r.spans if not s.name.startswith("sweep.")]
    assert read_metric("front_door_idle_ms.train", r) is None
    r = _reading({})
    assert read_metric("front_door_idle_ms.train", r) is None

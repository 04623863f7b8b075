"""The reduction from a profiler trace to device-time numbers."""
import glob
import json
import os

import pytest

from bench.trace import xplane
from bench.trace.xplane import Op, Reduction

DATA = os.path.join(os.path.dirname(__file__), "data")


def _key(num, wt):
    return bytes([(num << 3) | wt])


def _ld(num, payload: bytes) -> bytes:
    assert len(payload) < 128
    return _key(num, 2) + bytes([len(payload)]) + payload


def test_protobuf_reader_walks_an_hlo_proto():
    meta = _ld(2, b"jit(f)/alpha/dot_general")            # OpMetadata.op_name
    ins = _ld(1, b"fusion.3") + _ld(2, b"fusion") + _ld(7, meta)
    comp = _ld(1, b"main") + _ld(2, ins)
    module = _ld(1, b"jit_f") + _ld(3, comp)
    proto = _ld(1, module)
    assert xplane._instructions(proto, (0, len(proto))) == {
        "fusion.3": ("fusion", "jit(f)/alpha/dot_general")}
    assert xplane._varint(bytes([0xAC, 0x02]), 0) == (300, 2)


def _red():
    ops = [Op("while.1", 0, 100, "while", "", 0),
           Op("fusion.1", 0, 40, "fusion", "jit(f)/vmap(while)/body/alpha/dot_general", 0),
           Op("fusion.2", 50, 80, "fusion", "jit(f)/beta/mul", 0),
           Op("_rglru_scan.1", 150, 170, "custom-call", "jit(f)/beta/pallas_call", 0),
           Op("copy.1", 180, 190, "copy", "", 0)]
    host = [(0, 300, "bench.segment"), (100, 150, "gap.sleep"),
            (105, 140, "$time.py:1 sleep"), (190, 300, "serve.dispatch")]
    return Reduction(ops=ops, t0=0, t1=300, n_devices=1, host=host)


def test_known_sums_on_a_built_timeline():
    r = _red()
    assert r.window_s == pytest.approx(300e-9)
    assert r.busy_s == pytest.approx(130e-9)              # [0,100]+[150,170]+[180,190]
    assert r.scope_s("alpha") == pytest.approx(40e-9)
    assert r.scope_s("beta") == pytest.approx(50e-9)
    assert r.scope_s("gamma") is None
    assert r.kernel_s("_rglru_scan") == pytest.approx(20e-9)
    assert r.kernel_calls("_rglru_scan") == 1
    assert r.gaps() == [(100, 150), (170, 180), (190, 300)]
    gaps = r.top_gaps(2)
    assert gaps[0] == ["serve.dispatch|", pytest.approx(110e-9)]
    assert gaps[1] == ["gap.sleep|time.py:1 sleep", pytest.approx(50e-9)]
    top = r.top_ops(10)
    assert top[0] == ["alpha/dot_general", pytest.approx(40e-9)]
    assert [t[1] for t in top] == sorted((t[1] for t in top), reverse=True)
    assert "while" not in [t[0] for t in top]             # containers excluded


def _recorded():
    files = glob.glob(os.path.join(DATA, "small_trace", "**", "*.xplane.pb"),
                      recursive=True)
    assert files, "the recorded trace is missing (record_small_trace.py)"
    with open(os.path.join(DATA, "small_trace.json")) as f:
        return files[0], json.load(f)


def test_recorded_tpu_trace():
    path, want = _recorded()
    r = xplane.reduce_file(path)
    # every call ran the kernel once, inside the segment
    assert r.kernel_calls(want["kernel"]) == want["calls"]
    alpha, beta = r.scope_s("alpha"), r.scope_s("beta")
    assert alpha > 0 and beta > 0
    assert beta >= r.kernel_s(want["kernel"])             # the kernel is under beta
    # ops may overlap on the device, so a scope's sum is bounded by busy
    # time only one scope at a time
    assert max(alpha, beta) <= r.busy_s <= r.window_s
    # the host sleep is the longest idle gap, and is labelled as such
    label, secs = r.top_gaps(1)[0]
    assert label.startswith("gap.sleep") and secs >= want["sleep_s"]
    # busy time is the union of the op intervals, computed another way
    ivs = sorted((max(o.start, r.t0), min(o.end, r.t1)) for o in r.ops
                 if o.end > r.t0 and o.start < r.t1)
    tot, cur_s, cur_e = 0.0, None, None
    for s, e in ivs:
        if cur_e is None or s > cur_e:
            tot += 0 if cur_e is None else cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    tot += cur_e - cur_s
    assert r.busy_s == pytest.approx(tot * 1e-9)

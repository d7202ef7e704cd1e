"""The trace reduction on a small recorded trace: busy union, per-name
sums, container ops left out, module attribution and idle gaps."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import trace  # noqa: E402


@pytest.fixture(scope="module")
def tr():
    with open(os.path.join(BENCH, "tests", "small_trace.json")) as f:
        rec = json.load(f)
    mods = {int(k): [tuple(e) for e in v] for k, v in rec["modules"].items()}
    ops = {int(k): [tuple(e) for e in v] for k, v in rec["ops"].items()}
    return trace.reduce_events(mods, ops, [tuple(s) for s in rec["spans"]])


def test_window_and_busy_union(tr):
    assert tr.window == (0.0, 2.0)
    assert tr.n_devices == 2
    # device 0: 0.1 + 0.85 + 0.3; device 1: 1.4 + 0.1 (cut at the window's end)
    assert tr.busy_s == pytest.approx([1.25, 1.5])


def test_containers_dropped_and_kernels_by_name(tr):
    assert all(o.opcode != "while" for o in tr.ops)
    tgr = tr.op_seconds(lambda o: o.kernel and o.name.startswith("level_histograms"))
    tns = tr.op_seconds(lambda o: o.kernel and not o.name.startswith("level_histograms"))
    assert tgr == pytest.approx(0.3 + 0.5)
    assert tns == pytest.approx(0.05)
    assert tr.op_seconds(lambda o: o.collective) == pytest.approx(0.4)


def test_ops_attributed_to_their_module(tr):
    grow_xla = tr.op_seconds(lambda o: "grow_forest_impl" in o.module and not o.kernel)
    assert grow_xla == pytest.approx(0.2 + 0.4)     # fusion.181 on 0, all-reduce on 1
    route = [o for o in tr.ops if o.module == "jit_route_to_leaves"]
    assert [o.device for o in route] == [0, 1]


def test_result_shape_parsed(tr):
    tgr = [o for o in tr.ops if o.name == "level_histograms"]
    assert tgr[0].dims() == [32, 16, 128, 128]
    name, opcode, shape = trace.parse_op(
        "%fused_vote_scores.1 = f32[2,512]{1,0:T(2,128)S(1)} custom-call(s32[512,32]{1,0} %p)")
    assert (name, opcode) == ("fused_vote_scores", "custom-call")
    assert trace.Op(0, name, opcode, "", 0.0, 0.0, shape).dims() == [2, 512]


def test_idle_gaps_named_by_host_span_and_next_module(tr):
    names = [g[0] for g in tr.gaps]
    secs = [g[1] for g in tr.gaps]
    assert names == ["bench.job -> jit__grow_forest_impl", "bench.job -> jit_route_to_leaves",
                     "bench.job -> end of window"]
    assert secs == pytest.approx([0.05, 0.2, 0.5])


def test_breakdown_lists_largest_first(tr):
    b = trace.breakdown(tr)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "jit__grow_forest_impl/level_histograms[custom-call]"
    assert b["device_ops"][0][1] == pytest.approx((0.3 + 0.5) / 2)
    assert b["idle_gaps"][0] == ["bench.job -> end of window", pytest.approx(0.5)]

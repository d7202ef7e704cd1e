"""The program's spans and scopes read from a trace (``harness/scopes.py``)
on a small recorded trace, and the per-job split ``bench/scopes.py``
prints from them; the ``tf_op`` of each op read from the serialized
trace itself. Also pins what every existing trace reader reads on
``small_trace.json``: reading the program's names must not move them."""
import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402
import scopes as scopes_tool  # noqa: E402
from harness import scopes, trace  # noqa: E402


def _events(name):
    with open(os.path.join(BENCH, "tests", name)) as f:
        rec = json.load(f)
    mods = {int(k): [tuple(e) for e in v] for k, v in rec["modules"].items()}
    ops = {int(k): [tuple(e) for e in v] for k, v in rec["ops"].items()}
    return mods, ops, [tuple(s) for s in rec["spans"]]


@pytest.fixture(scope="module")
def sc():
    return scopes.reduce_events(*_events("small_trace_scoped.json"))


def test_scope_is_the_innermost_prf_segment():
    assert scopes.scope_of(
        "jit(f)/while/body/prf.task_group/while/body/prf.tgr/jit(level_histograms)/pallas_call"
    ) == "prf.tgr"
    assert scopes.scope_of("jit(apply_bins)/prf.bin.apply/lt") == "prf.bin.apply"
    assert scopes.scope_of("jit(_grow_forest_impl)/scatter") == ""
    assert scopes.scope_of("") == ""


def test_every_op_keeps_its_scope(sc):
    got = [(o.name, sc.scope(o)) for o in sc.trace.ops]
    assert got == [
        ("fusion", "prf.bin.apply"), ("sort", "prf.dsi"), ("fusion", "prf.dimred"),
        # starts with the while that holds it; the while is not an op
        ("fusion", ""),
        ("level_histograms", "prf.tgr"), ("prf.tns", "prf.tns"),
        ("fusion", ""),                             # tuple root: no op_name
        ("fusion", "prf.route"),
        ("select_negate_fusion", "prf.plan_write"),  # counts under its root's scope
        ("fusion", "prf.task_group"), ("copy", ""), ("dynamic-update-slice", ""),
        ("fusion", "prf.walk"), ("prf.walk", "prf.walk"),
        ("fusion", "prf.bin.apply"), ("level_histograms", "prf.tgr"),
    ]


def test_program_reading_leaves_the_trace_reduction_as_it_was(sc):
    mods, ops, spans = _events("small_trace_scoped.json")
    bench = [s for s in spans if s[0].startswith("bench.")]
    tr = trace.reduce_events(mods, {d: [o[:3] for o in v] for d, v in ops.items()}, bench)
    assert sc.trace == tr
    assert tr.gaps[0] == ("bench.job -> jit_apply_bins", 0.30)


def test_host_spans_kept_beside_the_benchmarks(sc):
    names = [n for n, _, _ in sc.spans]
    assert names[:4] == ["bench.window", "bench.job", "prf.train", "prf.screen"]
    assert sc.span_seconds("prf.bin.fit") == pytest.approx(0.23 + 0.24)
    assert sc.span_seconds("prf.oob") == pytest.approx(0.03)


def test_idle_gaps_named_after_the_innermost_span_over_each_piece(sc):
    gaps = {k: v for k, v in trace.top(sc.gaps(), n=20) if v > 1e-9}   # float dust
    assert gaps == pytest.approx({
        "prf.bin.fit -> jit_apply_bins": 0.47,
        "prf.screen -> jit_apply_bins": 0.07,
        "prf.bin.apply -> jit_apply_bins": 0.07,
        "bench.job -> jit_route_to_leaves": 0.03,
        "prf.train -> jit_apply_bins": 0.02,
        "bench.job -> jit_apply_bins": 0.02,
        "prf.bin.apply -> jit_bootstrap_counts": 0.02,
        "prf.grow -> jit__grow_forest_impl": 0.02,
        "prf.dsi -> jit__grow_forest_impl": 0.02,
        "prf.dimred -> jit__grow_forest_impl": 0.02,
    })
    # the same idle time as the benchmark's own gaps, only cut finer
    assert sum(s for _, s in sc.gaps()) == pytest.approx(sum(s for _, s in sc.trace.gaps))


# As a v5e trace holds an op's op_name: a tf_op stat of the event's
# metadata, interned (ref_value) or inline (str_value).
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 3000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_f(123)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"
    stats { metadata_id: 1 ref_value: 2 } stats { metadata_id: 3 int64_value: 7 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.1 = f32[8]{0} copy(f32[8]{0} %p)"
    stats { metadata_id: 1 str_value: "jit(f)/prf.b/copy:" } } }
  event_metadata { key: 4 value { id: 4 name: "%copy.2 = f32[8]{0} copy(f32[8]{0} %q)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "jit(f)/prf.a/add:" } }
  stat_metadata { key: 3 value { id: 3 name: "flops" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 600000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "prf.train" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(f)" } }
}
"""


def test_load_reads_each_ops_tf_op(tmp_path):
    from jax.profiler import ProfileData

    xspace = ProfileData.text_proto_to_serialized_xspace(XSPACE)
    assert [t for _, t in scopes.tf_ops(xspace)["/device:TPU:0"]] == [
        "jit(f)/prf.a/add:", "jit(f)/prf.b/copy:", ""]
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xspace)
    sc = scopes.load(str(tmp_path))
    assert [(o.name, o.module, sc.scope(o)) for o in sc.trace.ops] == [
        ("fusion", "jit_f", "prf.a"), ("copy", "jit_f", "prf.b"), ("copy", "jit_f", "")]
    assert [n for n, _, _ in sc.spans] == ["bench.window", "prf.train"]


def test_split_per_job(sc):
    got = scopes_tool.split(sc, jobs=2)
    ms = lambda s: 1e3 * s / 2  # noqa: E731
    assert got["engine.xla_ms_per_job"] == pytest.approx(ms(0.33))
    assert got["engine.task_group_xla_ms_per_job"] == pytest.approx(ms(0.05))
    assert got["engine.route_ms_per_job"] == pytest.approx(ms(0.15))
    assert got["engine.plan_write_ms_per_job"] == pytest.approx(ms(0.03))
    assert got["engine.unscoped_ms_per_job"] == pytest.approx(ms(0.10))
    assert got["oob.walk_ms_per_job"] == pytest.approx(ms(0.20))
    assert got["prep.device_ms_per_job"] == pytest.approx(ms(0.09 + 0.02 + 0.005 + 0.09))
    assert got["host_ms_per_job"]["prf.bin.fit"] == pytest.approx(ms(0.47))
    assert got["idle_gaps_ms_per_job"][0] == ["prf.bin.fit -> jit_apply_bins",
                                              pytest.approx(ms(0.47))]
    assert got["device_ms_per_job"]["jit__grow_forest_impl/prf.tgr[kernel]"] == \
        pytest.approx(ms(0.35))


@pytest.mark.parametrize("metric, value", [
    ("train.idle_pct", 31.25),
    ("train.mfu_pct", 0.1077042402930403),
    ("tgr.roofline_pct", 0.20207933577533577),
    ("tgr.ms_per_job", 200.0),
    ("tns.ms_per_job", 12.5),
    ("engine.xla_ms_per_job", 150.0),
    ("mesh.collective_ms_per_job", 100.0),
])
def test_existing_readers_read_what_they_read(metric, value):
    tr = trace.reduce_events(*_events("small_trace.json"))
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    rec = types.SimpleNamespace(
        trace=tr, driver=types.SimpleNamespace(jobs=[{}, {}]), peaks=peaks, chips=2,
        window_s=tr.window_s,
        shapes={"N": 262144, "F": 28, "k": 32, "D": 8, "B": 64, "C": 2, "m": 6,
                "frontier": 256, "chips": 2},
    )
    assert run.load_metric(metric).read(rec) == pytest.approx(value)

"""The mesh readers on a trace built from synthetic events: the T_GR
histogram combine (``mesh.hist_combine_ms_per_job``), the mesh
trainer's XLA ops (``mesh.xla_ms_per_job``), beside the readers the
mesh cell shares with the one-chip cell. Each op's duration is a power
of two, so a sum tells which ops a reader counted."""
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402
from harness import trace  # noqa: E402

SHAPES = {"N": 16384, "F": 2000, "k": 32, "D": 8, "B": 64, "C": 2, "m": 45,
          "frontier": 256, "chips": 4}
MESH = {"data": 2, "model": 2}
L = "{4,3,2,1,0:T(8,128)}"
OPS = {
    # name: (module, hlo text)
    "hist": ("jit_train", f"%psum.87 = f32[32,1000,2,64,256]{L} all-reduce(%f), "
                          "replica_groups={{0,2},{1,3}}"),
    "hist_rs": ("jit_train", f"%reduce-scatter.3 = f32[32,256,500,64,2]{L} "
                             "reduce-scatter(%f), dimensions={2}"),
    "hist_reuse": ("jit_train", f"%psum.90 = f32[32,128,1000,64,2]{L} all-reduce(%f)"),
    "winner_gather": ("jit_train", "%all-gather.22 = f32[64,1,256]{2,1,0} all-gather(%g), "
                                   "dimensions={0}"),
    "winner_psums": ("jit_train", "%all-reduce.55 = (f32[32,256]{1,0}, f32[32,256,2]{1,2,0}) "
                                  "all-reduce(%a, %b)"),
    "root_and_dimred": ("jit_train", "%all-reduce.52 = (f32[32,1,2]{0,2,1}, "
                                     f"f32[32,1,1000,64,2]{L}) all-reduce(%c, %d)"),
    "route_bit": ("jit_train", "%psum.88 = s32[32,8192]{1,0} all-reduce(%r)"),
    "tgr": ("jit_train", f"%level_histograms.3 = f32[32,256,1000,128]{L} custom-call(%x), "
                         'custom_call_target="tpu_custom_call"'),
    "tns": ("jit_train", "%closed_call.18 = (f32[32,1,256]{2,1,0}, s32[32,1,256]{2,1,0}) "
                         'custom-call(%h), custom_call_target="tpu_custom_call"'),
    "route": ("jit_train", "%fusion.5 = s32[32,8192]{1,0} fusion(%p), kind=kLoop"),
    "sketch_gather": ("jit__exchange", "%all-gather.1 = u32[2,2000,16385,4]{3,2,1,0} "
                                       "all-gather(%p), dimensions={0}"),
    "bins": ("jit_apply_bins", "%fusion.2 = u8[16384,2000]{1,0} fusion(%x), kind=kLoop"),
}
DUR = {name: 2.0 ** -(i + 1) for i, name in enumerate(OPS)}
JOBS = 2


def _rec(names, mesh=MESH):
    """Two devices run the named ops, each module once, one after another."""
    mods, ops = {}, {}
    for dev in (0, 1):
        t = 0.0
        for name in names:
            module, text = OPS[name]
            mods.setdefault(dev, []).append((f"{module}(7)", t, DUR[name]))
            ops.setdefault(dev, []).append((text, t, DUR[name]))
            t += 1.0
    tr = trace.reduce_events(mods, ops, [("bench.window", 0.0, 100.0)])
    return types.SimpleNamespace(trace=tr, driver=types.SimpleNamespace(jobs=[{}] * JOBS),
                                 shapes=SHAPES, traffic={"kind": "train_mesh", "mesh": mesh})


def _ms(*names):
    return pytest.approx(1e3 * sum(DUR[n] for n in names) / JOBS)


def _read(metric, rec):
    return run.load_metric(metric).read(rec)


def test_each_op_counted_by_the_reader_it_belongs_to():
    rec = _rec(list(OPS))
    hist = ("hist", "hist_rs", "hist_reuse")
    other = ("winner_gather", "winner_psums", "root_and_dimred", "route_bit", "sketch_gather")
    assert _read("mesh.hist_combine_ms_per_job", rec) == _ms(*hist)
    assert _read("mesh.collective_ms_per_job", rec) == _ms(*hist, *other)
    assert _read("mesh.xla_ms_per_job", rec) == _ms("route")
    assert _read("tgr.ms_per_job", rec) == _ms("tgr")
    assert _read("tns.ms_per_job", rec) == _ms("tns")
    # The XLA op of another program (binning) is the mesh trainer's by no reader.
    counted = sum(_read(m, rec) for m in ("mesh.collective_ms_per_job", "mesh.xla_ms_per_job",
                                          "tgr.ms_per_job", "tns.ms_per_job"))
    assert counted == _ms(*(n for n in OPS if n != "bins"))


@pytest.mark.parametrize("metric", ["mesh.hist_combine_ms_per_job", "mesh.xla_ms_per_job"])
def test_none_when_there_is_nothing_to_read(metric):
    assert _read(metric, types.SimpleNamespace(
        trace=None, driver=types.SimpleNamespace(jobs=[{}]), shapes=SHAPES, traffic={})) is None
    # A one-chip training trace: the kernel, XLA ops, no collective.
    assert _read(metric, _rec(["tgr", "tns", "route", "bins"])) is None


def test_hist_combine_needs_the_mesh_and_the_histogram_size():
    assert _read("mesh.hist_combine_ms_per_job", _rec(list(OPS), mesh=None)) is None
    assert _read("mesh.hist_combine_ms_per_job",
                 _rec(["winner_gather", "winner_psums", "root_and_dimred", "tgr"])) is None

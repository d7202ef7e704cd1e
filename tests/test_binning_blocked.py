"""Blocked/mergeable quantile-sketch binning (core/binning.py).

The out-of-core contract: `fit_bins_blocked` over per-block views is
bitwise identical to the resident `fit_bins` while summaries stay
uncompressed, deterministic always, block-bounded in memory (proved
against a memmap with tracemalloc), and composable — sketch merges,
validator exclusion masks, and the mesh exchange all reproduce the same
edges. Plus the uint8 bin-count guard and the float32 edge-boundary
contract of `apply_bins`.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForestConfig, train_prf
from repro.core.binning import (
    MAX_BINS,
    BinCountError,
    StreamingQuantileSketch,
    apply_bins,
    fit_bins,
    fit_bins_blocked,
    host_digitize,
)
from repro.data.pipeline import sample_blocks

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # CI installs hypothesis; the property test skips without
    HAVE_HYPOTHESIS = False

    def given(**kw):  # no-op decorators so the module still imports
        return lambda f: pytest.mark.skip(reason="hypothesis not installed")(f)

    settings = given

    class st:  # noqa: N801 - stand-in namespace
        @staticmethod
        def integers(*a, **kw):
            return None

        @staticmethod
        def sampled_from(*a, **kw):
            return None

        @staticmethod
        def booleans(*a, **kw):
            return None

SETTINGS = dict(max_examples=25, deadline=None)


def _rng(seed=0):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
# Exact-merge parity: blocked == resident, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize(
    "n_rows,block",
    [(600, 600), (600, 170), (601, 64), (601, 601), (37, 5), (4000, 333)],
)
def test_blocked_equals_exact_bitwise(dtype, n_rows, block):
    """Uncompressed sketch == np.quantile, to the last bit — single block,
    even blocks, and a ragged last block; both float dtypes (the lerp is
    evaluated in the source dtype, exactly as numpy does)."""
    x = (_rng(1).standard_normal((n_rows, 7))
         * 10.0 ** _rng(2).integers(-6, 6, (n_rows, 7))).astype(dtype)
    blocks = [x[i:i + block] for i in range(0, n_rows, block)]
    exact = fit_bins(x, 32)
    blocked = fit_bins_blocked(blocks, 32)
    assert blocked.dtype == exact.dtype == np.float64
    np.testing.assert_array_equal(blocked, exact)


@given(
    n_rows=st.integers(1, 400),
    block=st.integers(1, 400),
    n_bins=st.sampled_from([2, 8, 32]),
    wide=st.booleans(),
    ties=st.booleans(),
    seed=st.integers(0, 2**16),
)
@settings(**SETTINGS)
def test_blocked_equals_exact_property(n_rows, block, n_bins, wide, ties, seed):
    """Hypothesis sweep of the bitwise pin: any N, any block size (ragged
    last block / block > N / block == 1), heavy ties, wide exponents."""
    r = _rng(seed)
    x = r.standard_normal((n_rows, 3))
    if ties:
        x = np.round(x, 1)  # collapse to few distinct values
    if wide:
        x = x * 10.0 ** r.integers(-12, 12, x.shape)
    x = x.astype(np.float32)
    blocks = [x[i:i + block] for i in range(0, n_rows, block)]
    np.testing.assert_array_equal(
        fit_bins_blocked(blocks, n_bins), fit_bins(x, n_bins)
    )


def test_compressed_is_deterministic_and_close():
    """Past the compression threshold the sketch is no longer bitwise —
    but it is run-to-run deterministic and rank error stays bounded
    (< 2% of mass per edge at max_size=512 over 60k rows)."""
    x = _rng(3).standard_normal((60_000, 5)).astype(np.float32)
    blocks = [x[i:i + 4096] for i in range(0, x.shape[0], 4096)]
    a = fit_bins_blocked(blocks, 64, max_size=512)
    b = fit_bins_blocked(blocks, 64, max_size=512)
    np.testing.assert_array_equal(a, b)
    exact = fit_bins(x, 64)
    for f in range(x.shape[1]):
        for j in range(exact.shape[1]):
            lo, hi = sorted((exact[f, j], a[f, j]))
            frac = np.mean((x[:, f] > lo) & (x[:, f] <= hi))
            assert frac < 0.02, (f, j, frac)
    sk = StreamingQuantileSketch(5, max_size=512)
    for blk in blocks:
        sk.update(blk)
    assert not sk.exact
    assert int(sk.summary_sizes().max()) <= 2 * 512


def test_merge_matches_single_pass_and_roundtrips():
    """Sketch merge == one sketch over all blocks (bitwise, uncompressed),
    and the dense `state()` snapshot round-trips exactly — the mesh
    exchange depends on both."""
    x = _rng(4).standard_normal((500, 6)).astype(np.float32)
    left = StreamingQuantileSketch(6).update(x[:180])
    right = StreamingQuantileSketch(6).update(x[180:])
    merged = left.merge(right)
    single = StreamingQuantileSketch(6).update(x)
    np.testing.assert_array_equal(merged.edges(16), single.edges(16))
    np.testing.assert_array_equal(merged.edges(16), fit_bins(x, 16))
    assert merged.exact and int(merged.count.sum()) == 500 * 6

    back = StreamingQuantileSketch.from_state(merged.state(pad_to=1024))
    assert back.value_dtype == np.float32
    np.testing.assert_array_equal(back.edges(16), merged.edges(16))

    # Merging an empty sketch is a strict no-op (no dtype widening).
    merged.merge(StreamingQuantileSketch(6))
    assert merged.value_dtype == np.float32
    np.testing.assert_array_equal(merged.edges(16), single.edges(16))


def test_constant_and_empty_features():
    x = np.full((100, 2), 3.25, np.float32)
    x[:, 1] = 7.0
    blocked = fit_bins_blocked([x[:33], x[33:]], 8)
    np.testing.assert_array_equal(blocked, fit_bins(x, 8))
    assert np.all(blocked[0] == 3.25) and np.all(blocked[1] == 7.0)
    # A fully-excluded feature degrades to constant-0 edges, not a crash.
    mask = np.zeros_like(x, bool)
    mask[:, 0] = True
    e = fit_bins_blocked([x[:33], x[33:]], 8,
                         exclude_masks=[mask[:33], mask[33:]])
    assert np.all(e[0] == 0.0) and np.all(e[1] == 7.0)


def test_screened_cells_excluded_from_edges():
    """The validator's imputed-cell masks fold into the sketch: edges come
    from the surviving finite values only — bitwise equal to np.quantile
    over exactly those values — and bare NaN cells are dropped."""
    x = _rng(5).standard_normal((300, 4)).astype(np.float32)
    mask = _rng(6).random((300, 4)) < 0.1
    blocks = [x[:110], x[110:220], x[220:]]
    masks = {0: mask[:110], 2: mask[220:]}  # sparse, dict-keyed like api.py
    full_mask = np.zeros_like(mask)
    full_mask[:110] = mask[:110]
    full_mask[220:] = mask[220:]
    edges = fit_bins_blocked(blocks, 16, exclude_masks=masks)
    qs = np.linspace(0, 1, 17)[1:-1]
    for f in range(4):
        ref = np.quantile(x[~full_mask[:, f], f], qs)
        np.testing.assert_array_equal(edges[f], np.maximum.accumulate(ref))

    xn = x.copy()
    xn[full_mask] = np.nan  # same cells as NaN, no mask
    np.testing.assert_array_equal(fit_bins_blocked([xn], 16), edges)


# ---------------------------------------------------------------------------
# uint8 bin-count guard
# ---------------------------------------------------------------------------


def test_n_bins_validation_typed_error():
    x = _rng(7).standard_normal((64, 3)).astype(np.float32)
    for bad in (1, 0, -4, 257, 300, 2.5, "64", True):
        with pytest.raises(BinCountError):
            fit_bins(x, bad)
        with pytest.raises(BinCountError):
            fit_bins_blocked([x], bad)
        with pytest.raises(BinCountError):
            ForestConfig(n_bins=bad)
    with pytest.raises(ValueError):
        ForestConfig(bin_fit="fancy")
    # The boundary case must still work and stay inside uint8.
    edges = fit_bins(_rng(8).standard_normal((1000, 2)), MAX_BINS)
    assert edges.shape == (2, MAX_BINS - 1)
    ids = np.asarray(apply_bins(jnp.asarray(x[:, :2]), jnp.asarray(edges)))
    assert ids.dtype == np.uint8 and ids.max() <= MAX_BINS - 1


def test_apply_bins_rejects_wrapping_edges():
    """Pre-fix, 300 bins silently wrapped ids through the uint8 cast;
    now an over-wide edges array is a trace-time BinCountError."""
    with pytest.raises(BinCountError):
        apply_bins(jnp.zeros((4, 2), jnp.float32),
                   jnp.zeros((2, MAX_BINS), jnp.float32))


# ---------------------------------------------------------------------------
# float32 edge-boundary contract
# ---------------------------------------------------------------------------


def test_boundary_samples_follow_f32_contract():
    """Samples exactly on fitted edges: `apply_bins` evaluates both sides
    in float32 (explicitly — not via jax's implicit downcast), a sample
    bit-equal to edge j lands in bin j+1, and `host_digitize` is the
    host reference of the same rule."""
    # 101 rows put the 0.25/0.5/0.75 quantile positions on exact indices,
    # so the fitted edges are the data values themselves — 0.1, 0.3, 0.7,
    # none of which is float32-representable (0.7 rounds DOWN in f32).
    base = np.array([0.1] * 26 + [0.3] * 25 + [0.7] * 25 + [0.9] * 25)
    x = base[:, None].astype(np.float64)
    edges = fit_bins(x, 4)  # float64 edges, landing on data values
    np.testing.assert_array_equal(edges, [[0.1, 0.3, 0.7]])
    on_edge = edges.T.astype(np.float32)  # samples bit-equal (f32) to edges
    got = np.asarray(apply_bins(jnp.asarray(on_edge), jnp.asarray(edges)))
    np.testing.assert_array_equal(got, host_digitize(on_edge, edges))
    ef32 = edges.astype(np.float32)
    for j in range(edges.shape[1]):
        assert got[j, 0] == np.searchsorted(ef32[0], ef32[0, j], side="right")
    # The pin matters: comparing the same samples against the float64
    # edges lands at least one of them in a different bin (0.7's f32
    # rounding is below its f64 edge), which is the pre-fix ambiguity.
    f64_bins = np.stack(
        [np.searchsorted(edges[f], on_edge[:, f].astype(np.float64),
                         side="right") for f in range(edges.shape[0])], axis=1
    )
    assert not np.array_equal(got, f64_bins)


# ---------------------------------------------------------------------------
# sample_blocks: views, not copies
# ---------------------------------------------------------------------------


def test_sample_blocks_keeps_ndarray_identity_and_views(tmp_path):
    arr_blocks = [np.arange(6, dtype=np.float32).reshape(3, 2),
                  np.ones((2, 2), np.float32)]
    out = sample_blocks(arr_blocks)
    assert out[0] is arr_blocks[0] and out[1] is arr_blocks[1]
    # Non-array entries are materialized (once), arrays pass by identity.
    mixed = sample_blocks([arr_blocks[0], [[1.0, 2.0]]])
    assert mixed[0] is arr_blocks[0]
    assert isinstance(mixed[1], np.ndarray)

    p = tmp_path / "src.f32"
    mm = np.memmap(p, np.float32, "w+", shape=(10, 2))
    mm[:] = np.arange(20).reshape(10, 2)
    mm.flush()
    src = np.memmap(p, np.float32, "r", shape=(10, 2))
    views = sample_blocks(src, 4)
    assert len(views) == 3 and views[-1].shape == (2, 2)
    for v in views:
        assert np.shares_memory(v, src)


# ---------------------------------------------------------------------------
# Out-of-core: block-bounded memory against a memmap
# ---------------------------------------------------------------------------


def _fill_memmap(path, n_rows, n_features, seed=0):
    mm = np.memmap(path, np.float32, "w+", shape=(n_rows, n_features))
    r = _rng(seed)
    step = 100_000
    for i in range(0, n_rows, step):
        mm[i:i + step] = r.standard_normal(
            (min(step, n_rows - i), n_features), dtype=np.float32)
    mm.flush()
    del mm
    return np.memmap(path, np.float32, "r", shape=(n_rows, n_features))


def test_fit_bins_blocked_memmap_peak_memory(tmp_path):
    """The tentpole's memory bound: fitting edges over a 96MB memmap
    allocates O(block) + O(F * sketch) — a small fraction of the raw
    size — while the exact path demonstrably allocates the full copy
    (which also proves this measurement *can* detect materialization).

    tracemalloc is the meter (numpy registers its buffers with it);
    process RSS would be polluted by resident file pages, which the
    kernel reclaims lazily even though they are not allocations.
    """
    import tracemalloc

    n_rows, n_features = 1_000_000, 24
    src = _fill_memmap(tmp_path / "big.f32", n_rows, n_features)
    raw_bytes = n_rows * n_features * 4

    tracemalloc.start()
    blocked = fit_bins_blocked(sample_blocks(src, 65_536), 64)
    _, peak_blocked = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak_blocked < raw_bytes // 4, (
        f"blocked fit allocated {peak_blocked/1e6:.1f}MB against a "
        f"{raw_bytes/1e6:.0f}MB source — not block-bounded")

    tracemalloc.start()
    exact = fit_bins(src, 64)
    _, peak_exact = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak_exact >= raw_bytes, "meter failed to see the full-pass copy"
    assert peak_blocked < peak_exact // 8

    # Same source, same edges (uncompressed region is bitwise; this
    # scale compresses, so bound the rank error instead).
    sample = np.asarray(src[:4096])
    for f in range(0, n_features, 8):
        for j in range(0, 63, 16):
            lo, hi = sorted((exact[f, j], blocked[f, j]))
            frac = np.mean((sample[:, f] > lo) & (sample[:, f] <= hi))
            assert frac < 0.02


def test_streamed_train_memmap_peak_memory_and_determinism(tmp_path):
    """Acceptance: `train_prf(sample_block > 0)` on an np.memmap fits bin
    edges without materializing the raw source (host allocations stay
    far under the raw size; pre-fix, np.quantile copied all of it), and
    the model is bit-identical across reruns."""
    import tracemalloc

    n_rows, n_features = 250_000, 32
    src = _fill_memmap(tmp_path / "train.f32", n_rows, n_features, seed=1)
    raw_bytes = n_rows * n_features * 4
    y = _rng(2).integers(0, 3, n_rows).astype(np.int32)
    cfg = ForestConfig(n_trees=4, max_depth=2, n_bins=32, n_classes=3,
                       sample_block=50_000, feature_mode="all",
                       weighted_voting=False)
    assert cfg.resolved_bin_fit() == "blocked"

    tracemalloc.start()
    model = train_prf(src, y, cfg, seed=0)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < raw_bytes // 2, (
        f"streamed training allocated {peak/1e6:.1f}MB host-side against "
        f"a {raw_bytes/1e6:.0f}MB memmap — the raw source leaked into a "
        f"full-pass allocation")

    rerun = train_prf(src, y, cfg, seed=0)
    np.testing.assert_array_equal(model.bin_edges, rerun.bin_edges)
    for name in ("feature", "threshold", "left_child", "class_counts",
                 "value", "tree_weight"):
        np.testing.assert_array_equal(
            np.asarray(getattr(model.forest, name)),
            np.asarray(getattr(rerun.forest, name)), err_msg=name)


# ---------------------------------------------------------------------------
# Mesh plane: per-shard sketches merged over the collective gather
# ---------------------------------------------------------------------------


def test_fit_bins_sharded_matches_blocked_and_exact():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        from repro.core.binning import fit_bins, fit_bins_blocked
        from repro.core.distributed import fit_bins_sharded
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(2)
        x = rng.standard_normal((1000, 6)).astype(np.float32)
        mesh = make_mesh((4, 2), ("data", "model"))
        blocks = [x[i:i + 170] for i in range(0, 1000, 170)]

        e_sh = fit_bins_sharded(x, 32, mesh, sample_block=170)
        assert np.array_equal(e_sh, fit_bins_blocked(blocks, 32))
        assert np.array_equal(e_sh, fit_bins(x, 32))

        # Fewer blocks than data shards: the empty shard merges as a no-op.
        e1 = fit_bins_sharded(x, 16, mesh, sample_block=400)
        b1 = fit_bins_blocked([x[i:i + 400] for i in range(0, 1000, 400)], 16)
        assert np.array_equal(e1, b1)

        # Validator masks thread through, dict-keyed by global block index.
        m = {0: rng.random((170, 6)) < 0.05}
        e2 = fit_bins_sharded(x, 16, mesh, sample_block=170, exclude_masks=m)
        b2 = fit_bins_blocked(blocks, 16, exclude_masks=m)
        assert np.array_equal(e2, b2)

        # Samples sharded over BOTH mesh axes still merge in shard order.
        e3 = fit_bins_sharded(x, 16, mesh, sample_block=100,
                              sample_axes=("data", "model"))
        b3 = fit_bins_blocked([x[i:i + 100] for i in range(0, 1000, 100)], 16)
        assert np.array_equal(e3, b3)

        # A repeated fit on the same mesh compiles nothing.
        import jax
        compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _d, **_kw: compiles.append(event)
            if event == "/jax/core/compile/backend_compile_duration" else None)
        e4 = fit_bins_sharded(x, 32, mesh, sample_block=170)
        assert np.array_equal(e4, e_sh) and not compiles, compiles
        print("SHARDED_BINNING_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_BINNING_OK" in out.stdout


# One process holds every shard's sketch: the in-process merge against
# the exchange (pack, gather, unpack) on the same shard sketches.
SHARDED_CASES = {
    "exact": dict(n_bins=32, sample_block=170),
    # 250 rows per shard against 2 * 16 points: every summary recompresses.
    "compressed": dict(n_bins=16, sample_block=50, max_size=16),
    "fewer_blocks_than_shards": dict(n_bins=16, sample_block=400),
    "exclusion_masks": dict(n_bins=16, sample_block=170, masks=(0, 3)),
    "data_and_model": dict(n_bins=16, sample_block=100,
                           sample_axes=("data", "model")),
}

SHARDED_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import numpy as np
    from repro.core import distributed
    from repro.core.binning import DEFAULT_SKETCH_SIZE
    from repro.data.pipeline import sample_blocks
    from repro.launch.mesh import make_mesh

    cases, out = json.loads(sys.argv[1]), sys.argv[2]
    rng = np.random.default_rng(5)
    x = rng.standard_normal((1000, 6)).astype(np.float32)
    mesh = make_mesh((4, 2), ("data", "model"))

    def masks(case):
        return {i: rng.random((case["sample_block"], 6)) < 0.05
                for i in case.get("masks", ())}

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _d, **_kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    exchange = distributed._sketch_exchange

    def no_device(*a, **k):
        raise AssertionError("a single-process fit ran the sketch exchange")

    distributed._sketch_exchange = no_device
    res, ms = {}, {}
    for name, case in cases.items():
        ms[name] = masks(case)
        res[name + ".fit"] = distributed.fit_bins_sharded(
            x, case["n_bins"], mesh, sample_block=case["sample_block"],
            sample_axes=tuple(case.get("sample_axes", ("data",))),
            max_size=case.get("max_size"), exclude_masks=ms[name] or None)
    res["compiles_in_process"] = np.array(len(compiles))

    distributed._sketch_exchange = exchange
    for name, case in cases.items():
        axes = tuple(case.get("sample_axes", ("data",)))
        blocks = sample_blocks(x, case["sample_block"])
        n_shards = int(np.prod([mesh.shape[a] for a in axes]))
        local = [
            distributed._shard_sketch(
                blocks, p, 6, case.get("max_size") or DEFAULT_SKETCH_SIZE,
                ms[name] or None)
            for p in np.array_split(np.arange(len(blocks)), n_shards)
        ]
        wire = distributed._exchange_sketches(local, mesh, axes)
        res[name + ".compressed"] = np.array(not all(s.exact for s in wire))
        res[name + ".empty_shard"] = np.array(
            any(not s.count.any() for s in wire))
        res[name + ".wire"] = distributed._merge_edges(wire, case["n_bins"])
    np.savez(out, **res)
    print("SHARDED_MERGE_OK")
""")


@pytest.fixture(scope="module")
def sharded_merge(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("sharded") / "edges.npz")
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_SCRIPT, json.dumps(SHARDED_CASES), path],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_MERGE_OK" in out.stdout
    return dict(np.load(path))


@pytest.mark.parametrize("case", list(SHARDED_CASES))
def test_in_process_merge_equals_the_exchange_bitwise(sharded_merge, case):
    fit, wire = sharded_merge[case + ".fit"], sharded_merge[case + ".wire"]
    assert fit.dtype == wire.dtype == np.float64
    np.testing.assert_array_equal(fit, wire)
    assert bool(sharded_merge[case + ".compressed"]) == (case == "compressed")
    assert bool(sharded_merge[case + ".empty_shard"]) == (
        case == "fewer_blocks_than_shards")


def test_single_process_fit_runs_no_device_program(sharded_merge):
    # Every in-process fit above ran with `_sketch_exchange` patched to
    # raise, in a fresh process, before anything else touched the devices.
    assert int(sharded_merge["compiles_in_process"]) == 0

"""Bring-up smoke of the PRF train-and-serve path on a TPU.

    python chip_smoke.py [--seed N] [--chips 4 | --warm]

Runs in one process and starts none. With no option it drives the main
path on one chip, through the entry points a user calls, at the full
width the repo ships (``ForestConfig()``: 32 trees, depth 8, 64 bins,
2 classes) on seeded data at UCI HIGGS's published shape:

  data        28 dense real features, 2 near-balanced classes; the
              published 11,000,000 rows are cut to 1,000,000 training
              plus 100,000 held-out rows so the smoke fits its time;
  resident    ``train_prf`` with every backend on "auto" (the Pallas
              kernels on a TPU, checked compiled: ``tpu_custom_call``);
  reference   the same data and seed on the XLA backends
              (segment_sum / xla / xla): forests compared bitwise, and
              held-out labels compared within fixed bounds;
  streamed    ``train_prf`` from an ``np.memmap`` of the same rows in
              262,144-row blocks: forest bitwise equal to the resident
              one (edges fit exactly, as the resident path fits them);
  serve       ``PRFService(max_batch=1024)``: ``predict`` and
              ``submit``/``drain`` labels equal to ``model.predict``.

``--chips 4`` runs only the vertically partitioned mesh path and what it
is compared with: ``grow_forest_streamed`` on a (data=2,
model=2) mesh against ``grow_forest`` on one chip (bitwise), and
``make_sharded_vote_fn`` over the four chips against one-chip
``predict`` (labels equal).

``--warm`` runs only the resident "auto" ``train_prf`` and its held-out
``model.predict``, each twice in this process, so the second call of
each runs warm (compiled code in memory). Its times are set-up
evidence for where the time goes, not a benchmark.

Every other line is progress; the last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every check
passed. Without a TPU the script exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import numpy as np  # noqa: E402

HIGGS_ROWS = 11_000_000          # UCI HIGGS, published size
N_FEATURES = 28
N_TRAIN, N_TEST = 1_000_000, 100_000
SAMPLE_BLOCK = 262_144
SERVE_SIZES = (1, 37, 1024, 5000)
SUBMIT_SIZES = (5, 1, 64, 300, 17)
FOREST_ARRAYS = ("feature", "threshold", "left_child", "class_counts", "value",
                 "tree_weight")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class PhaseClock:
    """Wall time per phase, with the part XLA spent compiling (JAX's own
    monitoring event) reported separately."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compile_s += duration

    def run(self, name, fn):
        import jax

        c0, t0 = self.compile_s, time.perf_counter()
        out = fn()
        jax.block_until_ready(getattr(out, "forest", out))   # PRFModel
        wall = time.perf_counter() - t0
        log(f"[phase] {name}: wall {wall} s, of which compile "
            f"{self.compile_s - c0} s")
        return out


def make_data(seed: int):
    from repro.data.tabular import make_classification

    x, y = make_classification(
        n_samples=N_TRAIN + N_TEST, n_features=N_FEATURES, n_classes=2,
        seed=seed,
    )
    log(f"data: HIGGS shape (UCI): {HIGGS_ROWS:,} rows x {N_FEATURES} dense "
        f"real features, 2 classes; rows cut to {N_TRAIN:,} train + "
        f"{N_TEST:,} held out ({(N_TRAIN + N_TEST) / HIGGS_ROWS:.1%} of the "
        f"published rows) so the smoke fits its time; seed {seed}; "
        f"class-1 share {float(np.mean(y[:N_TRAIN]))}")
    return x[:N_TRAIN], y[:N_TRAIN], x[N_TRAIN:], y[N_TRAIN:]


def first_difference(a, b):
    """None if the forests are bitwise equal, else (array, tree, node);
    node is -1 for the per-tree ``tree_weight``."""
    for name in FOREST_ARRAYS:
        x, y = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        if x.shape != y.shape:
            return name, -1, -1
        diff = x != y
        if diff.ndim > 2:
            diff = diff.any(axis=tuple(range(2, diff.ndim)))
        if diff.any():
            at = np.argwhere(diff)[0]
            return name, int(at[0]), int(at[1]) if diff.ndim == 2 else -1
    return None


def lowered_has_kernel(lowered) -> bool:
    return "tpu_custom_call" in lowered.as_text()


def one_chip(seed: int, clock: PhaseClock) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import ForestConfig, train_prf
    from repro.core.forest import _grow_forest_impl
    from repro.core.gain import resolve_split_backend
    from repro.core.histograms import resolve_backend
    from repro.core.voting import resolve_predict_backend
    from repro.serving import PRFService

    x_tr, y_tr, x_te, y_te = clock.run("data", lambda: make_data(seed))

    # -- resident, auto ------------------------------------------------
    cfg = ForestConfig()
    backends = {
        "hist_backend": resolve_backend(cfg.hist_backend),
        "split_backend": resolve_split_backend(cfg.split_backend),
        "predict_backend": resolve_predict_backend(cfg.predict_backend),
    }
    log(f"resident: ForestConfig() = {cfg.n_trees} trees, depth "
        f"{cfg.max_depth}, {cfg.n_bins} bins, {cfg.n_classes} classes; "
        f"auto backends resolved to {backends}")
    check(set(backends.values()) == {"pallas"},
          f"auto backends did not all resolve to pallas: {backends}")
    model = clock.run(
        "resident train_prf (auto)", lambda: train_prf(x_tr, y_tr, cfg, seed=seed)
    )
    k, F = cfg.n_trees, N_FEATURES
    step = _grow_forest_impl.lower(
        jax.ShapeDtypeStruct((N_TRAIN, F), jnp.uint8),
        jax.ShapeDtypeStruct((N_TRAIN,), jnp.int32),
        jax.ShapeDtypeStruct((k, N_TRAIN), jnp.float32),
        config=cfg.resolved(F),
        feature_mask=jax.ShapeDtypeStruct((k, F), jnp.bool_),
    )
    step_compiled = lowered_has_kernel(step)
    log(f"resident: level step lowers to tpu_custom_call: {step_compiled}")
    check(step_compiled, "the level step holds no compiled Pallas kernel")
    labels = clock.run("resident predict (held out)", lambda: model.predict(x_te))
    acc = float(np.mean(labels == y_te))
    log(f"resident: held-out accuracy {acc}")

    # -- resident, reference backends ------------------------------------
    cfg_ref = dataclasses.replace(
        cfg, hist_backend="segment_sum", split_backend="xla",
        predict_backend="xla",
    )
    model_ref = clock.run(
        "resident train_prf (segment_sum/xla/xla)",
        lambda: train_prf(x_tr, y_tr, cfg_ref, seed=seed),
    )
    diff = first_difference(model.forest, model_ref.forest)
    log(f"reference: forests bitwise equal: {diff is None}")
    if diff is not None:
        log(f"reference: first difference in {diff[0]} at (tree, node) = "
            f"({diff[1]}, {diff[2]})")
    labels_ref = model_ref.predict(x_te)
    agree = float(np.mean(labels == labels_ref))
    acc_ref = float(np.mean(labels_ref == y_te))
    log(f"reference: held-out label agreement {agree} "
        f"({int(np.sum(labels == labels_ref))} of {N_TEST}); accuracy "
        f"auto {acc} vs reference {acc_ref}, gap {abs(acc - acc_ref)}")
    check(agree >= 0.99, f"held-out label agreement {agree} < 0.99")
    check(abs(acc - acc_ref) <= 0.005,
          f"held-out accuracy gap {abs(acc - acc_ref)} > 0.005")

    # -- streamed from a memmap ------------------------------------------
    # bin_fit="exact" fits the edges the resident path fits (the default
    # sketch is approximate past 8,192 rows per feature), so the two
    # forests are comparable bitwise.
    cfg_st = dataclasses.replace(cfg, sample_block=SAMPLE_BLOCK, bin_fit="exact")
    with tempfile.TemporaryDirectory(dir=REPO, prefix=".smoke-") as tmp:
        path = os.path.join(tmp, "x_train.f32")
        mm = np.memmap(path, np.float32, "w+", shape=x_tr.shape)
        mm[:] = x_tr
        mm.flush()
        del mm
        x_mm = np.memmap(path, np.float32, "r", shape=x_tr.shape)
        model_st = clock.run(
            f"streamed train_prf (memmap, sample_block={SAMPLE_BLOCK})",
            lambda: train_prf(x_mm, y_tr, cfg_st, seed=seed),
        )
        del x_mm
    diff = first_difference(model_st.forest, model.forest)
    log(f"streamed: forest bitwise equal to resident: {diff is None}"
        + ("" if diff is None else f" (first difference {diff})"))
    check(diff is None, f"streamed forest differs from resident: {diff}")

    # -- serve -----------------------------------------------------------
    svc = PRFService(model, max_batch=1024)

    def serve():
        out = {}
        for n in SERVE_SIZES:
            out[n] = svc.predict(x_te[:n])
        offsets = np.cumsum((0,) + SUBMIT_SIZES)
        futs = [svc.submit(x_te[o:o + n]) for o, n in zip(offsets, SUBMIT_SIZES)]
        svc.drain()
        return out, futs

    served, futs = clock.run("serve (predict + submit/drain)", serve)
    for n, got in served.items():
        check(np.array_equal(got, labels[:n]),
              f"served labels for a {n}-row request differ from model.predict")
    offsets = np.cumsum((0,) + SUBMIT_SIZES)
    for fut, o, n in zip(futs, offsets, SUBMIT_SIZES):
        check(fut.done(), f"a {n}-row submitted request never resolved")
        check(np.array_equal(fut.result(), labels[o:o + n]),
              f"submitted {n}-row request labels differ from model.predict")
    log(f"serve: predict of {list(SERVE_SIZES)} rows and {len(futs)} "
        f"submitted requests ({sum(SUBMIT_SIZES)} rows) equal model.predict")
    bucket = svc._bucket_predict.lower(
        svc._forest, svc._payload,
        jax.ShapeDtypeStruct((1024, F), jnp.uint8),
        jax.ShapeDtypeStruct((1024,), jnp.bool_),
    )
    bucket_compiled = lowered_has_kernel(bucket)
    log(f"serve: bucket program lowers to tpu_custom_call: {bucket_compiled}")
    check(bucket_compiled, "the service's bucket program holds no Pallas kernel")


def warm(seed: int, clock: PhaseClock) -> None:
    from repro.core import ForestConfig, train_prf

    x_tr, y_tr, x_te, y_te = clock.run("data", lambda: make_data(seed))
    cfg = ForestConfig()
    for i in (1, 2):
        model = clock.run(f"resident train_prf (auto) #{i}",
                          lambda: train_prf(x_tr, y_tr, cfg, seed=seed))
    for i in (1, 2):
        labels = clock.run(f"resident predict (held out) #{i}",
                           lambda: model.predict(x_te))
    log(f"warm: held-out accuracy {float(np.mean(labels == y_te))}")


def four_chips(seed: int, clock: PhaseClock) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import ForestConfig
    from repro.core.binning import apply_bins, bin_dataset
    from repro.core.dimred import dimension_reduction
    from repro.core.distributed import grow_forest_streamed
    from repro.core.dsi import bootstrap_counts
    from repro.core.forest import grow_forest
    from repro.core.voting import predict
    from repro.launch.mesh import make_mesh
    from repro.serving.prf_service import make_sharded_vote_fn

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, found "
          f"{len(jax.devices())}")
    x_tr, y_tr, x_te, _ = clock.run("data", lambda: make_data(seed))
    cfg = ForestConfig().resolved(N_FEATURES)
    xb_np, edges = bin_dataset(x_tr, cfg.n_bins)
    xb, y = jnp.asarray(xb_np), jnp.asarray(y_tr)
    k_boot, k_dim = jax.random.split(jax.random.PRNGKey(seed))
    w = bootstrap_counts(k_boot, cfg.n_trees, N_TRAIN)
    mask = dimension_reduction(xb, y, w, cfg, k_dim)

    f_one = clock.run(
        "one-chip grow_forest", lambda: grow_forest(xb, y, w, cfg, mask)
    )
    mesh = make_mesh((2, 2), ("data", "model"))
    log(f"mesh: {dict(mesh.shape)} over {[d.id for d in mesh.devices.flat]}")
    f_mesh = clock.run(
        "mesh grow_forest_streamed (data=2, model=2)",
        lambda: grow_forest_streamed(
            xb_np, y_tr, np.asarray(w),
            dataclasses.replace(cfg, sample_block=SAMPLE_BLOCK),
            np.asarray(mask), mesh=mesh,
        ),
    )
    diff = first_difference(f_mesh, f_one)
    log(f"mesh: forest bitwise equal to one-chip grow_forest: {diff is None}"
        + ("" if diff is None else f" (first difference {diff})"))
    check(diff is None, f"mesh forest differs from the one-chip forest: {diff}")

    xb_te = apply_bins(jnp.asarray(x_te), jnp.asarray(edges))
    labels_one = np.asarray(predict(f_one, xb_te))
    vote = make_sharded_vote_fn(f_one, make_mesh((4,), ("data",)), tree_axis="data")
    labels_four = clock.run("tree-sharded vote over 4 chips", lambda: vote(xb_te))
    same = np.array_equal(np.asarray(labels_four), labels_one)
    log(f"mesh: tree-sharded labels equal one-chip predict: {same} "
        f"({N_TEST} held-out rows)")
    check(same, "tree-sharded labels differ from one-chip predict")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--chips", type=int, choices=(1, 4), default=1)
    mode.add_argument("--warm", action="store_true",
                      help="time resident training and prediction twice each")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform = "
              f"{dev.platform!r}); nothing run", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; jax "
        f"{jax.__version__}; compile cache {enable_compile_cache()}")
    clock = PhaseClock()
    try:
        run = four_chips if args.chips == 4 else warm if args.warm else one_chip
        run(args.seed, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""A run with the timed path broken underneath comes out not correct.

Each test skips the look for a chip, drives the rest of a run at a tiny
size on the CPU with one fault planted in the program, and sees
``correct`` false: a job that returns its state unchanged, half of the
rows left out, an answer altered where it is produced, and (on four
virtual devices) the exchange between chips left out."""
import dataclasses
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import benchtiny  # noqa: E402
from repro.core import api  # noqa: E402
from repro.serving import prf_service  # noqa: E402

GROW = api.grow_forest


def unchanged(xb, y, w, cfg, mask):
    f = GROW(xb, y, w, cfg, mask)
    root = f.class_counts[:, :1]
    return dataclasses.replace(
        f, feature=jnp.full_like(f.feature, -1), left_child=jnp.full_like(f.left_child, -1),
        class_counts=jnp.zeros_like(f.class_counts).at[:, :1].set(root))


def half_rows(xb, y, w, cfg, mask):
    n = xb.shape[0] // 2
    return GROW(xb[:n], y[:n], w[:, :n], cfg, mask)


def altered(xb, y, w, cfg, mask):
    f = GROW(xb, y, w, cfg, mask)
    return dataclasses.replace(f, threshold=f.threshold.at[0, 0].add(1))


@pytest.mark.parametrize("fault", [unchanged, half_rows, altered], ids=lambda f: f.__name__)
def test_train_fault_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(api, "grow_forest", fault)
    r = benchtiny.execute("higgs-train")
    assert r["correct"] is False, r["checks"]


OOB = api.oob_accuracy


def test_oob_weights_on_in_bag_rows_are_not_correct(monkeypatch):
    """Eq. 8 taken over the rows a tree drew instead of those it never drew."""
    monkeypatch.setattr(api, "oob_accuracy",
                        lambda forest, xb, y, w: OOB(forest, xb, y, (w == 0).astype(w.dtype)))
    r = benchtiny.execute("higgs-train")
    assert r["correct"] is False and r["checks"]["weight_gap"]["value"] > 1e-3, r["checks"]


PREDICT = prf_service.PRFService._predict_bucketed


def label_altered(self, xb):
    out = PREDICT(self, xb).copy()
    out[0] = 1 - out[0]
    return out


def half_pass(self, xb):
    out = PREDICT(self, xb).copy()
    out[len(out) // 2:] = 0
    return out


@pytest.mark.parametrize("fault", [label_altered, half_pass], ids=lambda f: f.__name__)
def test_serve_fault_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(prf_service.PRFService, "_predict_bucketed", fault)
    r = benchtiny.execute("higgs-serve")
    assert r["correct"] is False, r["checks"]


NO_EXCHANGE = """
import json, sys
sys.path.insert(0, {here!r})
import benchtiny
from repro.core import distributed
init = distributed.MeshPlane.__init__
def local_only(self, *a, **k):
    init(self, *a, **k)
    self.combine_hist = lambda h: h          # the histogram psum left out
distributed.MeshPlane.__init__ = local_only
print(json.dumps(benchtiny.execute("epsilon-train-mesh4")))
"""


def test_mesh_without_exchange_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", NO_EXCHANGE.format(here=HERE)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["correct"] is False, r["checks"]

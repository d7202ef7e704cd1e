"""The one traffic generator: what every kind of traffic shares.

A traffic file (``bench/traffic/<name>.json``) names its ``kind`` and
the parameters of that kind; a configuration file
(``bench/configs/<name>.json``) gives the data shape and the forest.
A new mix of an existing kind is a new data file, nothing else. A kind
is a module of its own, ``bench/kinds/<kind>.py``, whose ``Driver``
class ``load`` finds by the name; a new kind (another training plane,
another arrival process) is a new module there, subclassing one of the
bases here:

* ``TrainJobs``: whole training jobs back to back, from the same raw
  rows on the host to a weighted forest, each with its own seed; a kind
  gives the job (``make_job``).
* ``ServeOpen``: requests of held-out rows at fixed arrivals (open loop)
  through ``PRFService.submit``, with a server thread that calls
  ``drain()`` whenever requests are pending; a kind may give other
  arrivals (``arrival_gaps``).

Every driver makes its inputs from the run's seed, warms up every shape
its window uses, measures for the window, and then hands what it
produced to the plain reference (``harness.reference``).
"""
from __future__ import annotations

import gc
import importlib.util
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import reference as ref
from .clock import now, span
from .data import make_classification, sub_seed

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def forest_config(cfg: dict):
    from repro.core import ForestConfig

    return ForestConfig(
        n_trees=cfg["n_trees"], max_depth=cfg["max_depth"], n_bins=cfg["n_bins"],
        n_classes=cfg["n_classes"], feature_mode=cfg["feature_mode"],
        weighted_voting=cfg["weighted_voting"], hist_reuse=cfg["hist_reuse"],
        tree_chunk=cfg.get("tree_chunk", 0), min_gain=cfg["min_gain"],
        min_samples_split=cfg["min_samples_split"],
    )


def make_rows(cfg: dict, n_rows: int, seed: int):
    g = cfg["generator"]
    return make_classification(
        n_rows, cfg["n_features"], cfg["n_classes"], n_informative=g["n_informative"],
        n_redundant=g["n_redundant"], class_sep=g["class_sep"],
        label_noise=g["label_noise"], seed=seed,
    )


class Driver:
    """What every kind shares: the cell, the seed, the records."""

    def __init__(self, cell: dict, cfg: dict, traffic: dict, seed: int, seconds: float,
                 devices):
        self.cell, self.cfg, self.traffic, self.seed = cell, cfg, traffic, seed
        self.seconds, self.devices = seconds, devices
        self.spans: list = []
        self.limits = {**cfg.get("limits", {}), **traffic.get("limits", {})}

    def shapes(self) -> dict:
        c = self.cfg
        m, _ = ref.resolved_sizes(c, c["n_features"])
        return {
            "N": c["n_rows"], "F": c["n_features"], "k": c["n_trees"], "D": c["max_depth"],
            "B": c["n_bins"], "C": c["n_classes"], "m": m,
            "frontier": 2 ** c["max_depth"], "chips": len(self.devices),
        }


def load(kind: str):
    """The ``Driver`` class of ``bench/kinds/<kind>.py``."""
    path = os.path.join(BENCH, "kinds", f"{kind}.py")
    if not os.path.exists(path):
        raise KeyError(f"no traffic kind {kind!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(f"bench_kind_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Driver


class TrainJobs(Driver):
    shards = 1          # data shards of the bootstrap (the mesh's data axis)

    def setup(self):
        self.prepare()
        with span("warmup", self.spans):
            self.job(sub_seed(self.seed, 4))

    def prepare(self):
        """Data, job seeds and the program's job, with nothing run yet."""
        c = self.cfg
        self.x, self.y = make_rows(c, c["n_rows"], sub_seed(self.seed, 1))
        self.job_seeds = np.random.default_rng(sub_seed(self.seed, 2)).integers(
            0, 2**31 - 1, size=4096)
        self.fc = forest_config(c)
        self.job = self.make_job()

    def make_job(self):
        """A function of a job seed that runs one whole job, blocked on its
        forest, and returns ``(forest, bin_edges)``."""
        raise NotImplementedError

    def window(self, seconds: float):
        self.jobs = []
        t0 = now()
        with span("window", self.spans):
            while True:
                s = self.job_seeds[len(self.jobs)]
                t = now()
                with span("job", self.spans):
                    out = self.job(s)
                t1 = now()
                self.jobs.append({"seed": int(s), "start": t, "end": t1, "out": out})
                if t1 - t0 >= seconds:
                    break
        self.window_s = t1 - t0

    def end_to_end(self) -> dict:
        rows, trees = self.cfg["n_rows"], self.cfg["n_trees"]
        return {"train_rowtrees_per_s": len(self.jobs) * rows * trees / self.window_s}

    def release(self):
        """Keep one job, drawn from the seed, on the host; free the rest."""
        pick = int(np.random.default_rng(sub_seed(self.seed, 3)).integers(0, len(self.jobs)))
        forest, edges = self.jobs[pick]["out"]
        self.sample = {
            "seed": self.jobs[pick]["seed"],
            "edges": np.asarray(edges),
            "pool": [np.asarray(a) for a in (forest.feature, forest.threshold,
                                             forest.left_child, forest.class_counts,
                                             forest.tree_weight)],
        }
        for j in self.jobs:
            j.pop("out")
        self.job = None
        gc.collect()

    def _rc(self) -> dict:
        return {k: self.cfg[k] for k in ("n_bins", "n_classes", "max_depth", "n_trees",
                                         "min_gain", "min_samples_split")}

    def _inputs(self, seed: int, precision: str = "float64"):
        """The reference's edges, bins, DSI counts and feature masks."""
        return ref.train_job(self.x, self.y, self._rc(), seed, shards=self.shards,
                             precision=precision)

    def judge(self, edges, got: "ref.HeapForest", detail: bool = False) -> dict:
        """Every number compared, for a forest and its bin edges."""
        r_edges, xb, w, masks = self._inputs(self.sample["seed"])
        return {"edges_differ": ref.edge_mismatch(r_edges, edges),
                **ref.check_forest(xb, self.y, w, masks, self._rc(), got, detail)}

    def _got(self) -> "ref.HeapForest":
        return ref.heap_from_pool(*self.sample["pool"], self.cfg["max_depth"])

    def check(self, detail: bool = False) -> dict:
        """The sampled job against the reference."""
        return self.judge(self.sample["edges"], self._got(), detail=detail)

    def control(self, detail: bool = False) -> dict:
        """The reference computed in bfloat16, put in the program's place."""
        c_edges, c_xb, c_w, c_masks = self._inputs(self.sample["seed"], "bfloat16")
        ctl = ref.train(c_xb, self.y, c_w, c_masks, self._rc(), "bfloat16")
        return self.judge(c_edges, ctl, detail=detail)

    def witness(self) -> dict:
        """The sampled job's trees against trees the reference grows itself."""
        _, xb, w, masks = self._inputs(self.sample["seed"])
        want = ref.train(xb, self.y, w, masks, self._rc())
        return ref.first_differences(xb, self.y, w, masks, self._rc(), want, self._got())

    def attempted(self) -> tuple[int, int]:
        return len(self.jobs), 0


# ---------------------------------------------------------------------------
# Online scoring
# ---------------------------------------------------------------------------


def bucket_size(n: int, min_bucket: int, max_batch: int) -> int:
    b = 1 << max(n - 1, 0).bit_length()
    return max(min_bucket, min(b, max_batch))


def generate_forest(cfg: dict, seed: int):
    """A forest at the configuration's shape, built on the device in one
    jitted call from the seed: every node above the last level split,
    in the program's node-pool layout (level ``L``'s children in band
    ``1 + 2 * (frontier / 2) * L``; right child = left + 1). Tree weights
    are multiples of 1/1024, so every weighted vote sums exactly."""
    import jax
    import jax.numpy as jnp

    k, D, F, B, C = (cfg[n] for n in ("n_trees", "max_depth", "n_features", "n_bins",
                                       "n_classes"))
    half = 2 ** D // 2
    P = 1 + 2 * half * D + 1
    lvl = np.full(P, -1, np.int64)
    pos = np.zeros(P, np.int64)
    lvl[0] = 0
    for L in range(1, D + 1):
        ids = 1 + 2 * half * (L - 1) + np.arange(2 ** L)
        lvl[ids], pos[ids] = L, np.arange(2 ** L)
    internal = (lvl >= 0) & (lvl < D)
    used = lvl >= 0
    left = np.where(internal, 1 + 2 * half * lvl + 2 * pos, -1)

    @jax.jit
    def build(key):
        kf, kt, kc, kw = jax.random.split(key, 4)
        feat = jax.random.randint(kf, (k, P), 0, F, dtype=jnp.int32)
        thr = jax.random.randint(kt, (k, P), 0, B - 1, dtype=jnp.int32)
        counts = jax.random.randint(kc, (k, P, C), 1, 1000).astype(jnp.float32)
        w = jax.random.randint(kw, (k,), 512, 1025).astype(jnp.float32) / 1024.0
        inner = jnp.asarray(internal)[None, :]
        return (
            jnp.where(inner, feat, -1),
            jnp.where(inner, thr, 0),
            jnp.broadcast_to(jnp.asarray(left, jnp.int32)[None, :], (k, P)),
            jnp.where(jnp.asarray(used)[None, :, None], counts, 0.0),
            jnp.zeros((k, P), jnp.float32),
            w,
        )

    return build(jax.random.PRNGKey(seed))


class Request:
    __slots__ = ("due", "sent", "start", "done", "offset", "rows", "fut", "labels", "error")

    def __init__(self, due, offset, rows):
        self.due, self.offset, self.rows = due, offset, rows
        self.sent = self.start = self.done = None
        self.fut = self.labels = self.error = None


class ServeOpen(Driver):
    def setup(self):
        import jax

        from repro.core import Forest
        from repro.core.api import PRFModel
        from repro.serving import PRFService

        c, t = self.cfg, self.traffic
        self.pool, _ = make_rows(c, t["pool_rows"], sub_seed(self.seed, 1))
        self.edges = ref.fit_edges(self.pool[: t["edge_rows"]], c["n_bins"])
        arrays = generate_forest(c, sub_seed(self.seed, 5))
        jax.block_until_ready(arrays)
        self.forest_host = [np.asarray(a) for a in arrays]
        forest = Forest(*arrays, config=forest_config(c).resolved(c["n_features"]))
        self.svc = PRFService(PRFModel(forest=forest, bin_edges=self.edges),
                              max_batch=t["max_batch"], min_bucket=t["min_bucket"])
        self.requests = self.schedule()
        # A drain serves whatever is queued, and submit drains by itself once
        # max_batch rows wait, so a pass holds any total up to max_batch - 1
        # + the largest request. The service compiles its binning and
        # padding for every such total, so each is a shape of the window.
        # They compile independently and the compiler runs outside the
        # interpreter lock, so they are warmed from a pool of threads.
        sizes = range(1, t["max_batch"] + t["rows_max"])
        with span("warmup", self.spans), ThreadPoolExecutor(os.cpu_count() or 8) as pool:
            for _ in pool.map(lambda n: self.svc.predict(self.pool[:n]), sizes):
                pass

    def arrival_gaps(self, rng, n: int, rate: float) -> np.ndarray:
        """Seconds between consecutive arrivals: Poisson at ``rate``."""
        return rng.exponential(1.0 / rate, n)

    def schedule(self):
        """Arrivals at ``rate_per_s`` over the window; each request takes
        ``rows_min``..``rows_max`` rows, log-uniform, from a random offset of
        the held-out pool. A traffic file leaves ``rate_per_s`` out until
        the serving knee has been measured (``bench/sweep.py``)."""
        t = self.traffic
        if "rate_per_s" not in t:
            raise KeyError("traffic has no rate_per_s: measure the knee with bench/sweep.py")
        rng = np.random.default_rng(sub_seed(self.seed, 2))
        n = int(t["rate_per_s"] * self.seconds * 1.5) + 64
        due = np.cumsum(self.arrival_gaps(rng, n, t["rate_per_s"]))
        lo, hi = t["rows_min"], t["rows_max"]
        rows = np.floor(np.exp(rng.uniform(np.log(lo), np.log(hi + 1), n))).astype(np.int64)
        offs = rng.integers(0, self.pool.shape[0] - hi, n)
        keep = due < self.seconds
        return [Request(float(d), int(o), int(r))
                for d, o, r in zip(due[keep], offs[keep], rows[keep])]

    def _stamp(self, started: float, outstanding: list, lock) -> None:
        t = now()
        with lock:
            still = []
            for r in outstanding:
                if r.fut is not None and r.fut.done():
                    r.start, r.done = started, t
                else:
                    still.append(r)
            outstanding[:] = still

    def window(self, seconds: float):
        lock = threading.Lock()
        outstanding: list = []
        stop = threading.Event()
        self.errors: list = []
        poll = self.traffic["poll_s"]

        def server():
            while not (stop.is_set() and not self.svc.pending):
                if self.svc.pending:
                    t = now()
                    try:
                        self.svc.drain()
                    except Exception as e:          # counted, never hidden
                        self.errors.append(repr(e))
                    self._stamp(t, outstanding, lock)
                else:
                    time.sleep(poll)

        with span("window", self.spans):
            th = threading.Thread(target=server, name="bench-drain", daemon=True)
            t0 = now()
            th.start()
            for r in self.requests:
                wait = t0 + r.due - now()
                if wait > 0:
                    time.sleep(wait)
                r.sent = now() - t0
                with lock:
                    outstanding.append(r)
                t = now()
                try:
                    r.fut = self.svc.submit(self.pool[r.offset:r.offset + r.rows])
                except Exception as e:
                    r.error = repr(e)
                    with lock:
                        outstanding.remove(r)
                    continue
                if r.fut.done():
                    self._stamp(t, outstanding, lock)
            self.window_s = max(self.seconds, now() - t0)
            # Every answer due in the window is waited for, a minute at most.
            deadline = now() + 60.0
            while outstanding and now() < deadline:
                time.sleep(0.01)
            stop.set()
            th.join(timeout=60.0)
        for r in self.requests:
            if (r.done is not None and r.fut.done() and r.fut.exception() is None):
                r.labels = np.asarray(r.fut.result())
                r.start -= t0
                r.done -= t0

    def served(self):
        return [r for r in self.requests if r.labels is not None]

    def end_to_end(self) -> dict:
        lat = [(r.done - r.due) * 1e3 for r in self.served()]
        return {"serve_p95_ms": float(np.percentile(lat, 95))} if lat else {}

    def release(self):
        self.stats = self.svc.stats()
        self.svc = None
        gc.collect()

    def _reference(self, x: np.ndarray, precision: str) -> np.ndarray:
        f = self.forest_host
        return ref.predict_pool(f[0], f[1], f[2], f[3], f[5], self.edges, x,
                                self.cfg["max_depth"], precision)

    def _served_rows(self):
        served = self.served()
        x = np.concatenate([self.pool[r.offset:r.offset + r.rows] for r in served])
        return x, np.concatenate([r.labels.reshape(-1) for r in served])

    def check(self) -> dict:
        """Every served request against the reference."""
        x, got = self._served_rows()
        return {"label_mismatch_rows": int(np.sum(self._reference(x, "float64") != got))}

    def control(self) -> dict:
        """The reference in bfloat16 put in the program's place."""
        x, _ = self._served_rows()
        want = self._reference(x, "float64")
        return {"label_mismatch_rows": int(np.sum(self._reference(x, "bfloat16") != want))}

    def attempted(self) -> tuple[int, int]:
        failed = sum(1 for r in self.requests if r.labels is None)
        return len(self.requests), failed

    def groups(self):
        """Rows per forward pass: requests stamped by one drain."""
        by = {}
        for r in self.served():
            by.setdefault(r.start, []).append(r.rows)
        return [sum(v) for v in by.values()]


"""Multi-process training plane bootstrap (paper §4 at cluster scale).

One process per host, ``jax.distributed`` coordination, a **global** mesh
over every process's devices — and the invariant that makes the
out-of-core trainer scale: *each process constructs only its addressable
slice of every array*. Host→device feed bandwidth and host RAM then
multiply by process count instead of funneling through one machine.

Three layers live here:

* :func:`initialize` — coordinator bootstrap around
  ``jax.distributed.initialize`` (CPU collectives forced to gloo, per-host
  virtual device count via ``XLA_FLAGS``). Call it before any other jax
  use in the process.
* :class:`MultiHostMesh` — extends ``launch.mesh`` meshes to the global
  device set with addressable-shard introspection: which contiguous range
  of the sample-axis shards this process owns, the local row range of any
  padded global array, and ``put``/``zeros`` constructors built on
  ``jax.make_array_from_callback`` so only local bytes ever leave this
  host. ``psum_hosts`` union-reduces small integer vectors exactly
  (16-bit limbed int32 psum — no x64 dependence), and doubles as the
  cross-process barrier.
* Multi-process checkpointing — process-0 manifests with per-host shard
  leaves (``save_checkpoint_multiproc`` / ``restore_checkpoint_multiproc``
  / :class:`MultiprocCheckpointManager`): replicated leaves are written
  once by process 0, sample-sharded leaves once per process, all under
  the single-process format's atomic tmp-dir + rename protocol with
  per-leaf CRC32s. Restoring across a *changed* process count raises
  :class:`repro.checkpoint.checkpoint.CheckpointTopologyError` — never a
  silently wrong forest. (Single-machine shared-filesystem layout; on a
  real cluster the per-host leaves would go to per-host object-store
  prefixes — the manifest protocol is the same.)
"""
from __future__ import annotations

import os
import shutil
import warnings
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import make_mesh


def initialize(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    *,
    local_device_count: Optional[int] = None,
) -> Tuple[int, int]:
    """Bootstrap this process into a ``jax.distributed`` runtime.

    Must run before any jax backend use in the process.
    ``local_device_count`` forces that many virtual host-platform devices
    per process (the CPU drill topology: N processes x M devices); on
    real accelerators leave it ``None`` and let the backend discover the
    local devices. CPU collectives are switched to gloo, the only
    cross-process CPU implementation. Returns
    ``(process_index, process_count)``.
    """
    if local_device_count is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{local_device_count}"
            ).strip()
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return jax.process_index(), jax.process_count()


def is_multiprocess() -> bool:
    """True when this jax runtime spans more than one process."""
    return jax.process_count() > 1


def _resolve(sl: slice, dim: int) -> Tuple[int, int]:
    """A shard-index slice as concrete ``(start, stop)``."""
    return (
        0 if sl.start is None else int(sl.start),
        dim if sl.stop is None else int(sl.stop),
    )


def _local_box(sharding, shape) -> List[Tuple[int, int]]:
    """Bounding box (per-dim ``(lo, hi)``) of this process's addressable
    shards of a global array with ``sharding``/``shape``."""
    imap = sharding.addressable_devices_indices_map(tuple(shape))
    lo = [int(d) for d in shape]
    hi = [0] * len(shape)
    for idx in imap.values():
        for d, sl in enumerate(idx):
            st, sp = _resolve(sl, shape[d])
            lo[d] = min(lo[d], st)
            hi[d] = max(hi[d], sp)
    return list(zip(lo, hi))


class MultiHostMesh:
    """A global device mesh plus this process's place in it.

    Extends ``launch.mesh`` to multi-process runtimes: the mesh spans
    every process's devices (process-major, so the default
    ``(n_devices, 1)`` data x model layout gives each process a
    *contiguous* range of sample-axis shards), and the class knows which
    shard range — and therefore which global row range — belongs to this
    process. All host→device constructors go through
    ``jax.make_array_from_callback``, which asks only for the addressable
    shards: remote rows are never touched on this host (the whole point —
    an ``np.memmap`` source only pages in local rows).

    ``feed_bytes`` counts every byte this process handed to its local
    devices through the runtime (the example's per-host feed report).
    """

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        *,
        sample_axes: Sequence[str] = ("data",),
        feature_axis: str = "model",
    ):
        if mesh is None:
            mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
        self.mesh = mesh
        self.sample_axes = tuple(sample_axes)
        self.feature_axis = feature_axis
        self.process_index = int(jax.process_index())
        self.process_count = int(jax.process_count())
        self.feed_bytes = 0
        self._jit_cache: dict = {}

        names = list(mesh.axis_names)
        spos = [names.index(a) for a in self.sample_axes]
        opos = [i for i in range(len(names)) if i not in spos]
        devs = np.asarray(mesh.devices)
        D = int(np.prod([devs.shape[i] for i in spos]))
        rows = np.transpose(devs, spos + opos).reshape(D, -1)
        owned = []
        for d in range(D):
            procs = {int(dev.process_index) for dev in rows[d]}
            if self.process_index in procs:
                if procs != {self.process_index}:
                    raise ValueError(
                        f"sample-axis shard {d} spans processes "
                        f"{sorted(procs)} — the multi-process plane needs "
                        "each sample shard pinned to one process (use the "
                        "default process-major (n_devices, 1) mesh)"
                    )
                owned.append(d)
        if not owned:
            raise ValueError(
                f"process {self.process_index} owns no sample-axis shard of "
                f"mesh {dict(zip(names, devs.shape))}"
            )
        if owned != list(range(owned[0], owned[-1] + 1)):
            raise ValueError(
                f"process {self.process_index}'s sample-axis shards {owned} "
                "are not contiguous — local memmap row ranges require a "
                "process-major device order"
            )
        self.n_data_shards = D
        self.shard_lo, self.shard_hi = owned[0], owned[-1] + 1

    # -- row bookkeeping -------------------------------------------------

    def pad(self, n_rows: int) -> int:
        """Rows of padding that make ``n_rows`` divide the data shards."""
        return (-n_rows) % self.n_data_shards

    def local_row_range(self, n_rows_padded: int) -> Tuple[int, int]:
        """This process's ``[lo, hi)`` rows of a padded global row dim."""
        if n_rows_padded % self.n_data_shards:
            raise ValueError(
                f"{n_rows_padded} rows do not divide {self.n_data_shards} "
                "sample shards — pad first (see .pad())"
            )
        rps = n_rows_padded // self.n_data_shards
        return self.shard_lo * rps, self.shard_hi * rps

    # -- local-slice array constructors ---------------------------------

    def put(self, host: np.ndarray, global_shape, spec, *, box=None):
        """Build a global device array from this process's host bytes.

        ``host`` holds the **local box** of the global array — ``box``
        gives its per-dim ``(lo, hi)`` position in global coordinates
        (``None`` means ``host`` is the full array, e.g. a replicated
        leaf). The callback only ever receives addressable-shard indices,
        so nothing outside the box is read.
        """
        host = np.asarray(host)
        global_shape = tuple(int(s) for s in global_shape)
        sh = NamedSharding(self.mesh, spec)

        def cb(index):
            idx, shard_shape = [], []
            for d, sl in enumerate(index):
                st, sp = _resolve(sl, global_shape[d])
                off = 0 if box is None else box[d][0]
                idx.append(slice(st - off, sp - off))
                shard_shape.append(sp - st)
            # reshape pins the exact shard rank: ascontiguousarray
            # promotes 0-d (scalar leaves) to (1,), which the runtime
            # would reject as a shard-shape mismatch.
            out = np.ascontiguousarray(host[tuple(idx)]).reshape(shard_shape)
            self.feed_bytes += out.nbytes
            return out

        return jax.make_array_from_callback(global_shape, sh, cb)

    def put_full(self, host, spec):
        """Replicate/shard a host array every process holds in full."""
        host = np.asarray(host)
        return self.put(host, host.shape, spec)

    def zeros(self, global_shape, spec, dtype=jnp.float32):
        """A zero-filled global array, materialized shard-by-shard."""
        global_shape = tuple(int(s) for s in global_shape)
        sh = NamedSharding(self.mesh, spec)

        def cb(index):
            shape = []
            for d, sl in enumerate(index):
                st, sp = _resolve(sl, global_shape[d])
                shape.append(sp - st)
            return np.zeros(tuple(shape), dtype)

        return jax.make_array_from_callback(global_shape, sh, cb)

    def block_placement(self, padded_rows: Sequence[int], n_features: int,
                        x_spec) -> Callable:
        """A ``BlockFeeder`` placement callback: block ``i``'s host-local
        rows become the global ``[m_i, F]`` device block. The feeder
        passes ``(host_local_block, block_index)``."""
        padded_rows = [int(m) for m in padded_rows]

        def place(host_local, index):
            m = padded_rows[index]
            lo, hi = self.local_row_range(m)
            if host_local.shape[0] != hi - lo:
                raise ValueError(
                    f"block[{index}]: host-local rows {host_local.shape[0]} "
                    f"!= local range {hi - lo} of {m} padded rows"
                )
            return self.put(
                host_local, (m, n_features), x_spec,
                box=[(lo, hi), (0, n_features)],
            )

        return place

    # -- exact cross-process reductions ---------------------------------

    def psum_hosts(self, vec) -> np.ndarray:
        """Exact global sum of one small int vector per process.

        Values are split into 16-bit limbs and summed with an int32
        ``psum`` (exact without x64 for per-process values < 2**48),
        each process contributing exactly once. Every process must call
        this collectively; it doubles as the cross-process barrier."""
        v = np.asarray(vec, np.int64).ravel()
        limbs = np.stack(
            [v & 0xFFFF, (v >> 16) & 0xFFFF, (v >> 32) & 0xFFFF], axis=1
        ).astype(np.int32)                                       # [n, 3]
        D = self.n_data_shards
        n = limbs.shape[0]
        sh = NamedSharding(self.mesh, P(self.sample_axes))
        mine = self.shard_lo

        def cb(index):
            d, _ = _resolve(index[0], D)
            if d == mine:
                return limbs[None]
            return np.zeros((1, n, 3), np.int32)

        g = jax.make_array_from_callback((D, n, 3), sh, cb)
        key = ("psum_hosts", n)
        fn = self._jit_cache.get(key)
        if fn is None:
            def kernel(x_loc):
                return jax.lax.psum(x_loc[0], self.sample_axes)

            fn = jax.jit(jax.shard_map(
                kernel, mesh=self.mesh,
                in_specs=(P(self.sample_axes),), out_specs=P(),
                check_vma=False,
            ))
            self._jit_cache[key] = fn
        out = np.asarray(jax.device_get(fn(g))).astype(np.int64)  # [n, 3]
        return out[:, 0] + (out[:, 1] << 16) + (out[:, 2] << 32)

    def barrier(self) -> None:
        """Block until every process reaches this point."""
        self.psum_hosts(np.zeros(1, np.int64))


# ---------------------------------------------------------------------------
# Multi-process checkpointing (process-0 manifest, per-host shard leaves)
# ---------------------------------------------------------------------------


def _host_view(leaf):
    """``(is_full, host_array, box)`` of one pytree leaf on this process.

    Fully-replicated (and plain host) leaves come back whole; sharded
    leaves come back as the local bounding box assembled from the
    addressable shards, with coverage verified (a gap would checkpoint
    uninitialized memory)."""
    if not isinstance(leaf, jax.Array) or leaf.is_fully_replicated:
        if isinstance(leaf, jax.Array):
            return True, np.asarray(jax.device_get(leaf)), None
        return True, np.asarray(leaf), None
    shards = leaf.addressable_shards
    if not shards:
        raise ValueError(
            "checkpoint leaf has no addressable shards on process "
            f"{jax.process_index()} — every leaf of a multi-process "
            "checkpoint must be replicated or sample-sharded"
        )
    shape = leaf.shape
    lo = [int(s) for s in shape]
    hi = [0] * leaf.ndim
    resolved = []
    for s in shards:
        idx = [_resolve(sl, shape[d]) for d, sl in enumerate(s.index)]
        for d, (st, sp) in enumerate(idx):
            lo[d] = min(lo[d], st)
            hi[d] = max(hi[d], sp)
        resolved.append(idx)
    box_shape = tuple(h - l for l, h in zip(lo, hi))
    buf = np.empty(box_shape, leaf.dtype)
    covered = np.zeros(box_shape, np.bool_)
    for s, idx in zip(shards, resolved):
        sl = tuple(slice(st - l, sp - l) for (st, sp), l in zip(idx, lo))
        buf[sl] = np.asarray(s.data)
        covered[sl] = True
    if not covered.all():
        raise ValueError(
            "addressable shards leave gaps in the local box "
            f"{list(zip(lo, hi))} of a {shape} leaf — refusing to "
            "checkpoint uninitialized memory"
        )
    return False, buf, list(zip(lo, hi))


def _sub_manifest_name(pid: int) -> str:
    return f"shards.p{pid:02d}.msgpack"


def save_checkpoint_multiproc(
    tree, directory: str, step: int, runtime: MultiHostMesh,
) -> str:
    """Collective atomic save: every process writes its shard leaves,
    process 0 writes the replicated leaves + the manifest and performs
    the atomic rename. Barriers order create → write → rename, so a
    reader never sees a torn step and a crash leaves only an orphaned
    ``.tmp_save_*`` dir (cleaned up like the single-process format's).
    """
    from ..checkpoint.checkpoint import _TMP_PREFIX, _crc32

    os.makedirs(directory, exist_ok=True)
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = os.path.join(directory, f"{_TMP_PREFIX}step_{step:08d}")
    if runtime.process_index == 0:
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
    runtime.barrier()                       # tmp dir exists everywhere

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    pid = runtime.process_index
    manifest = {
        "step": step,
        "topology": {"process_count": runtime.process_count},
        "leaves": [],
    }
    sub = {"process": pid, "leaves": []}
    for i, (path, leaf) in enumerate(flat):
        key = "/".join(
            str(getattr(p, "key", getattr(p, "idx", p))) for p in path
        )
        full, arr, box = _host_view(leaf)
        if full:
            fname = f"leaf_{i:05d}.npy"
            if pid == 0:
                np.save(os.path.join(tmp, fname), arr)
                manifest["leaves"].append({
                    "key": key, "file": fname, "dtype": str(arr.dtype),
                    "shape": list(arr.shape), "crc32": _crc32(arr),
                })
        else:
            fname = f"leaf_{i:05d}.p{pid:02d}.npy"
            np.save(os.path.join(tmp, fname), arr)
            sub["leaves"].append({
                "key": key, "file": fname, "dtype": str(arr.dtype),
                "shape": list(arr.shape),
                "box": [[int(l), int(h)] for l, h in box],
                "crc32": _crc32(arr),
            })
            if pid == 0:
                manifest["leaves"].append({
                    "key": key, "sharded": True, "dtype": str(arr.dtype),
                    "shape": [int(s) for s in leaf.shape],
                })
    with open(os.path.join(tmp, _sub_manifest_name(pid)), "wb") as f:
        f.write(msgpack.packb(sub))
    if pid == 0:
        with open(os.path.join(tmp, "manifest.msgpack"), "wb") as f:
            f.write(msgpack.packb(manifest))
    runtime.barrier()                       # every process done writing
    if pid == 0:
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
    runtime.barrier()                       # final dir visible everywhere
    return final


def _load_sub_manifest(path: str, pid: int) -> dict:
    from ..checkpoint.checkpoint import CheckpointCorruptionError

    try:
        with open(os.path.join(path, _sub_manifest_name(pid)), "rb") as f:
            sub = msgpack.unpackb(f.read())
        if not isinstance(sub, dict) or "leaves" not in sub:
            raise ValueError("shard manifest has no leaves")
        return sub
    except CheckpointCorruptionError:
        raise
    except Exception as e:
        raise CheckpointCorruptionError(
            f"torn or unreadable shard manifest for process {pid} in "
            f"{path}: {e}"
        ) from e


def _verify_local(path: str, runtime: MultiHostMesh) -> None:
    """CRC/shape/dtype-verify the leaves this process would restore."""
    from ..checkpoint.checkpoint import (
        _check_topology, _load_leaf, _load_manifest,
    )

    manifest = _load_manifest(path)
    _check_topology(manifest, path)
    sub = _load_sub_manifest(path, runtime.process_index)
    by_key = {e["key"]: e for e in sub["leaves"]}
    for entry in manifest["leaves"]:
        if entry.get("sharded"):
            local = by_key.get(entry["key"])
            if local is None:
                from ..checkpoint.checkpoint import CheckpointCorruptionError

                raise CheckpointCorruptionError(
                    f"sharded leaf {entry['key']!r} missing from process "
                    f"{runtime.process_index}'s shard manifest in {path}"
                )
            _load_leaf(path, local)
        else:
            _load_leaf(path, entry)


def restore_checkpoint_multiproc(
    tree_like, directory: str, step: Optional[int] = None,
    shardings=None, *, runtime: MultiHostMesh, verify: bool = True,
):
    """Multi-process restore: replicated leaves load from process 0's
    files (every process reads the shared step dir), sharded leaves from
    this process's own shard files — re-assembled into global arrays via
    the runtime's local-slice ``put``. The saved local box must match
    the current sharding's box exactly (same process count and mesh), or
    :class:`CheckpointTopologyError` is raised."""
    from ..checkpoint.checkpoint import (
        CheckpointCorruptionError, CheckpointTopologyError, _check_topology,
        _load_leaf, _load_manifest, latest_step,
    )

    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {directory}")
    path = os.path.join(directory, f"step_{step:08d}")
    manifest = _load_manifest(path)
    _check_topology(manifest, path)
    sub = _load_sub_manifest(path, runtime.process_index)
    sub_by_key = {e["key"]: e for e in sub["leaves"]}
    by_key = {e["key"]: e for e in manifest["leaves"]}

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree_like)
    shard_flat = None
    if shardings is not None:
        sflat, _ = jax.tree_util.tree_flatten_with_path(shardings)
        shard_flat = [s for _, s in sflat]

    leaves = []
    for i, (pth, like) in enumerate(flat):
        key = "/".join(
            str(getattr(p, "key", getattr(p, "idx", p))) for p in pth
        )
        entry = by_key.get(key)
        if entry is None:
            raise CheckpointCorruptionError(
                f"leaf {key!r} missing from manifest in {path}"
            )
        sh = shard_flat[i] if shard_flat is not None else None
        spec = sh.spec if sh is not None else P()
        if not entry.get("sharded"):
            if verify:
                arr = _load_leaf(path, entry)
            else:
                arr = np.load(os.path.join(path, entry["file"]))
            leaves.append(runtime.put_full(arr, spec))
            continue
        local = sub_by_key.get(key)
        if local is None:
            raise CheckpointCorruptionError(
                f"sharded leaf {key!r} missing from process "
                f"{runtime.process_index}'s shard manifest in {path}"
            )
        arr = _load_leaf(path, local) if verify else np.load(
            os.path.join(path, local["file"])
        )
        gshape = [int(s) for s in entry["shape"]]
        if sh is None:
            raise ValueError(
                f"sharded leaf {key!r} needs an explicit sharding to "
                "restore onto (pass `shardings`)"
            )
        want = _local_box(sh, gshape)
        got = [tuple(b) for b in local["box"]]
        if [tuple(b) for b in want] != got:
            raise CheckpointTopologyError(
                f"sharded leaf {key!r} in {path} was saved with local box "
                f"{got} but this runtime's sharding expects {want} — the "
                "mesh layout changed; resume on the saving topology"
            )
        leaves.append(runtime.put(arr, gshape, spec, box=want))
    return jax.tree_util.tree_unflatten(treedef, leaves), step


def restore_latest_valid_multiproc(
    tree_like, directory: str, shardings, runtime: MultiHostMesh,
):
    """Collective ``restore_latest_valid``: every process verifies its
    own leaves of each step (newest first) and the verdicts are
    union-reduced, so all processes agree on the step they restore —
    one host's corrupt shard walks *everyone* back together. Topology
    mismatches propagate (they apply to every step; walking back would
    silently retrain a stale carry). Returns ``(tree, step)`` or
    ``None`` when nothing verifies anywhere."""
    from ..checkpoint.checkpoint import (
        CheckpointCorruptionError, list_steps,
    )

    for step in reversed(list_steps(directory)):
        path = os.path.join(directory, f"step_{step:08d}")
        try:
            _verify_local(path, runtime)
            ok = 1
        except (CheckpointCorruptionError, OSError, ValueError, KeyError):
            ok = 0
        agree = int(runtime.psum_hosts(np.asarray([ok]))[0])
        if agree == runtime.process_count:
            return restore_checkpoint_multiproc(
                tree_like, directory, step, shardings,
                runtime=runtime, verify=False,
            )
        warnings.warn(
            f"skipping checkpoint step {step} in {directory}: only "
            f"{agree}/{runtime.process_count} processes verified it",
            RuntimeWarning,
            stacklevel=2,
        )
    return None


class MultiprocCheckpointManager:
    """Rotating multi-process checkpoints — the drop-in counterpart of
    ``checkpoint.CheckpointManager`` for the multi-process growth plane.
    Process 0 owns orphan cleanup, garbage collection, and the manifest;
    saves and restores are collective (every process participates)."""

    def __init__(
        self, directory: str, keep: int = 3, save_interval: int = 100,
        *, runtime: MultiHostMesh,
    ):
        from ..checkpoint.checkpoint import _TMP_PREFIX

        self.directory = directory
        self.keep = keep
        self.save_interval = save_interval
        self.runtime = runtime
        if runtime.process_index == 0 and os.path.isdir(directory):
            for d in os.listdir(directory):
                if d.startswith(_TMP_PREFIX):
                    shutil.rmtree(
                        os.path.join(directory, d), ignore_errors=True
                    )
        runtime.barrier()

    def maybe_save(self, tree, step: int) -> Optional[str]:
        if step % self.save_interval != 0:
            return None
        path = save_checkpoint_multiproc(
            tree, self.directory, step, self.runtime
        )
        if self.runtime.process_index == 0:
            self._gc()
        self.runtime.barrier()
        return path

    def _gc(self):
        from ..checkpoint.checkpoint import list_steps

        for s in list_steps(self.directory)[: -self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"))

    def restore_latest_valid(self, tree_like, shardings=None):
        out = restore_latest_valid_multiproc(
            tree_like, self.directory, shardings, self.runtime
        )
        if out is None:
            raise FileNotFoundError(
                f"no valid checkpoint in {self.directory}"
            )
        return out

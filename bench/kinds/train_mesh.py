"""Training jobs on a (data, model) mesh, as ``examples/prf_distributed.py``
runs them: ``fit_bins_sharded``, ``apply_bins``, then the trainer of
``make_prf_train_fn``. The traffic file gives the mesh shape."""
from harness.clock import span
from harness.drivers import TrainJobs


class Driver(TrainJobs):
    def make_job(self):
        import jax
        import jax.numpy as jnp

        from repro.core.binning import apply_bins
        from repro.core.distributed import fit_bins_sharded, make_prf_train_fn
        from repro.launch.mesh import make_mesh

        shape = self.traffic["mesh"]
        mesh = make_mesh((shape["data"], shape["model"]), ("data", "model"))
        train_fn, _ = make_prf_train_fn(self.fc, mesh)
        n_bins, sketch = self.cfg["n_bins"], self.cfg["sketch_max_size"]
        block = self.x.shape[0] // shape["data"]
        y_dev = jnp.asarray(self.y)
        self.shards = shape["data"]

        def job(s):
            with span("fit_bins_sharded", self.spans):
                edges = fit_bins_sharded(self.x, n_bins, mesh, sample_block=block,
                                         max_size=sketch)
            with span("apply_bins", self.spans):
                xb = apply_bins(jnp.asarray(self.x), jnp.asarray(edges))
            with span("train_fn", self.spans):
                forest = train_fn(xb, y_dev, jax.random.PRNGKey(int(s)))
                jax.block_until_ready(forest)
            return forest, edges

        return job

"""Training jobs on one chip: ``train_prf`` on the resident path."""
from harness.drivers import TrainJobs


class Driver(TrainJobs):
    def make_job(self):
        import jax

        from repro.core import train_prf

        def job(s):
            model = train_prf(self.x, self.y, self.fc, seed=int(s))
            jax.block_until_ready(model.forest)
            return model.forest, model.bin_edges

        return job

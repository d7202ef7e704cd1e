"""Online scoring at Poisson arrivals, open loop, through ``PRFService``."""
from harness.drivers import ServeOpen as Driver  # noqa: F401

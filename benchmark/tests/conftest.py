"""The benchmark's own tests run on the CPU: JAX is held to it before
anything imports it."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

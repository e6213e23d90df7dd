"""Shared fixtures.  NOTE: no device-count XLA_FLAGS here — smoke tests
and benches must see 1 CPU device; only launch/dryrun.py forces 512.

The one flag set here turns off XLA's YNNPACK fusions on the CPU.  Where
the host CPU supports them, XLA fuses a dot through YNNPACK in some
programs and not in others, so two jitted programs that the tests hold
bitwise equal (pipelined vs sequential search) round differently in the
last bit depending on the host.  Without them every host runs XLA's own
CPU code generation."""
import os

os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS"), "--xla_cpu_experimental_ynn_fusion_type="]))

import jax  # noqa: E402  (after XLA_FLAGS: the backend reads them once)
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)

import jax
import pytest

from repro.core.compile_cache import enable_compile_cache
from repro.distributed import sharding as shd

# NOTE: no XLA_FLAGS here on purpose — tests run on the real single CPU
# device; only launch/dryrun.py forces 512 host devices (assignment step 0).

# Persistent XLA compilation cache: the fast tier is compile-bound on CPU, so
# repeated runs (local dev, the tier-1 gate) skip recompilation entirely.
enable_compile_cache()


@pytest.fixture(scope="session")
def mesh():
    m = shd.device_mesh(jax.devices()[0])
    shd.set_mesh(m)
    with m:
        yield m


@pytest.fixture(autouse=True)
def _mesh_ctx(mesh):
    # every test runs inside the 1-device mesh context
    shd.set_mesh(mesh)
    yield

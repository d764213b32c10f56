import jax
import pytest


@pytest.fixture(scope="module")
def x64():
    """64-bit JAX for the orbital-mechanics tests, switched off again when
    the module finishes. The flag is process-wide and every xdist worker
    imports every test file, so turning it on at import would run the
    whole suite (kernel compiles included) with 64-bit scalars."""
    with jax.enable_x64(True):
        yield

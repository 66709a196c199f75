import jax


def test_backend_is_virtual_cpu_mesh():
    """conftest must pin tests to a virtual 8-device CPU mesh (the real TPU
    is reached through chiprun; multi-chip sharding is tested virtually)."""
    assert jax.default_backend() == "cpu"
    assert len(jax.devices()) == 8

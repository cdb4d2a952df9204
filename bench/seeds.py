"""Keys from the benchmark's seeds."""


def seed_key(seed: int):
    """A JAX key for any whole seed >= 0, also past 32 bits."""
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)

"""Token ids uniform over the model's vocabulary; no parameters."""


def validate(params: dict) -> None:
    if params:
        raise ValueError(f"uniform tokens take no parameters: {params}")


def device(key, shape, vocab: int, params: dict):
    """A jax draw of int32 ids of ``shape``, inside a jitted program."""
    import jax
    import jax.numpy as jnp
    return jax.random.randint(key, shape, 0, vocab, jnp.int32)


def host(rng, n: int, vocab: int, params: dict):
    """``n`` int32 ids from a numpy generator."""
    return rng.integers(0, vocab, n, dtype="int32")

"""Token ids skewed by Zipf's law: the id of rank r (from 1) is drawn
with odds proportional to r ** -a.  Which id holds which rank is one fixed
permutation of the vocabulary, the same for every seed, so that every seed
offers the same hot tokens.  Parameters: ``a`` (> 0)."""


def validate(params: dict) -> None:
    a = params.get("a")
    if set(params) != {"a"} or not isinstance(a, (int, float)) or a <= 0:
        raise ValueError(f"zipf tokens take one exponent a > 0: {params}")


def device(key, shape, vocab: int, params: dict):
    """A jax draw of int32 ids of ``shape``, inside a jitted program."""
    import jax
    import jax.numpy as jnp

    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    cdf = jnp.cumsum(ranks ** -params["a"])
    u = jax.random.uniform(key, shape) * cdf[-1]
    rank = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
    perm = jax.random.permutation(jax.random.key(0), vocab)
    return perm[rank].astype(jnp.int32)


def host(rng, n: int, vocab: int, params: dict):
    """``n`` int32 ids from a numpy generator."""
    import numpy as np

    cdf = np.cumsum(np.arange(1, vocab + 1, dtype=np.float64) ** -params["a"])
    rank = np.minimum(np.searchsorted(cdf, rng.random(n) * cdf[-1]),
                      vocab - 1)
    perm = np.random.default_rng(0).permutation(vocab)
    return perm[rank].astype(np.int32)

"""``--seed`` is any whole number up to a little over 2**31; JAX's key takes
31 bits without complaint, so the rest is folded in."""


def seed_key(seed: int):
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)

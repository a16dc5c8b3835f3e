"""One rule for turning a run's ``--seed`` into a JAX key seed.

``--seed`` may need more than 32 bits; a JAX key built with 64-bit
integers off takes only the low 32 of them, so seeds that differ in the
high bits would get the same weights. Fold the high bits in instead.
"""


def key_seed(seed: int) -> int:
    seed = int(seed)
    return (seed ^ (seed >> 31) ^ (seed >> 62)) & 0x7FFFFFFF

"""Pallas TPU kernels (validated on CPU via interpret=True) + jnp oracles.

Signing requests route through ``dispatch`` (see README.md for the policy);
``autotune`` owns block-size selection; ``packfmt`` is the b-bit packed-code
format shared by the store, the scorers and the signing paths' ``pack_b``.
"""

from .ops import (cminhash_signatures, cminhash_signatures_packed,  # noqa: F401
                  collision_counts, estimated_jaccard_matrix)

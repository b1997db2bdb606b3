"""repro — 2.5D communication-reducing block-sparse SpGEMM (DBCSR, PASC'17)
re-built as a TPU-native JAX framework, plus the multi-arch LM stack that
integrates the paper's distribution technique."""
import jax as _jax

__version__ = "1.0.0"

# Sharding-invariant PRNG: without partitionable threefry, `jax.random`
# draws depend on the out_sharding, which breaks layout-equivalence
# guarantees this repo relies on (ZeRO-1 init == replicated init,
# cross-mesh checkpoint restore).  It is the default from jax 0.5; the
# update only guards against a config that turned it off.
if not _jax.config.jax_threefry_partitionable:
    _jax.config.update("jax_threefry_partitionable", True)

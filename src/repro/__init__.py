"""repro — paper reproduction package.

Sharding-invariant RNG is load-bearing for the whole repo: with the legacy
non-partitionable threefry, GSPMD splits the RNG counter differently per
out-sharding, so ZeRO-3's dp-sharded parameter init would draw *different
values* than stage 0/1 on the same seed. JAX's default
(``jax_threefry_partitionable=True``) makes random draws a pure function of
(key, shape) regardless of mesh/sharding; do not turn it off.
"""

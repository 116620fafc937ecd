"""What ``memory_stats()`` counts on this runtime: run one program whose
temporaries are ~6 GiB and whose arguments and results are tiny, and print
every key before and after."""
import json

import jax
import jax.numpy as jnp

d = jax.devices()[0]
print("before", json.dumps(d.memory_stats()))
n = 768 * 2**20  # 3 GiB of float32


@jax.jit
def f(key):
    x = jax.random.uniform(key, (n,), jnp.float32)
    return jnp.sum(jnp.sort(x)[:: n // 8])


c = f.lower(jax.random.PRNGKey(0)).compile()
m = c.memory_analysis()
print("compiled: temp", m.temp_size_in_bytes, "args", m.argument_size_in_bytes, "out", m.output_size_in_bytes)
print(float(f(jax.random.PRNGKey(0))))
print("after", json.dumps(d.memory_stats()))
live = jnp.ones((256 * 2**20,), jnp.float32) + 1  # 1 GiB live array
live.block_until_ready()
print("with 1 GiB live", json.dumps(d.memory_stats()))

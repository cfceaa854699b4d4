import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import allgather_matmul, ring_allgather_matmul

mesh = make_mesh((4,), ("model",))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
w = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)

def base(xs, w):
    return allgather_matmul(xs, w, "model")
def ring(xs, w):
    return ring_allgather_matmul(xs, w, "model")

fb = jax.jit(jax.shard_map(base, mesh=mesh, in_specs=(P("model"), P()),
                           out_specs=P(), check_vma=False))
fr = jax.jit(jax.shard_map(ring, mesh=mesh, in_specs=(P("model"), P()),
                           out_specs=P(), check_vma=False))
want = np.asarray(x) @ np.asarray(w)
np.testing.assert_allclose(np.asarray(fb(x, w)), want, rtol=1e-5, atol=1e-5)
np.testing.assert_allclose(np.asarray(fr(x, w)), want, rtol=1e-5, atol=1e-5)
print("OK")

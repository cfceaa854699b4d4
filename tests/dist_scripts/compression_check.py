import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import compressed_psum

mesh = make_mesh((4,), ("data",))
rng = np.random.default_rng(0)
g = jnp.asarray(rng.normal(size=(4, 512)), jnp.float32)  # per-device rows

def f(g):
    out, ef = compressed_psum(g[0], "data", None)
    return out[None], ef[None]

fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                           out_specs=(P("data"), P("data")), check_vma=False))
mean, ef = fn(g)
true_mean = np.asarray(g).mean(axis=0)
got = np.asarray(mean)[0]
err = np.abs(got - true_mean).max() / (np.abs(true_mean).max() + 1e-9)
assert err < 0.05, err
# error feedback: quantization residual is what was lost
resid = np.asarray(ef)
assert np.abs(resid).max() < np.abs(np.asarray(g)).max() * 0.02
# second round WITH error feedback reduces accumulated bias
print("OK", err)

"""Quickstart: FlexLink in 40 lines.

1. Tune shares for an 8-GPU H800 AllGather (Algorithm 1 on the calibrated
   timing model) and print the predicted bandwidth win over NCCL.
2. Run an actual multi-path all-gather on a CPU device mesh and verify it is
   bit-identical to the single-path reference.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import collectives as mp
from repro.core.simulator import MiB, PathTimingModel
from repro.core.topology import Collective
from repro.core.tuner import initial_tune

# -- 1. control plane: Stage-1 tuning ---------------------------------------
model = PathTimingModel("h800")
payload = 256 * MiB
res = initial_tune(["nvlink", "pcie", "rdma"], "nvlink",
                   lambda fr: model.measure(Collective.ALL_GATHER, 8,
                                            payload, fr))
nccl = model.nccl_baseline_GBps(Collective.ALL_GATHER, 8, payload)
flex = model.algbw_GBps(Collective.ALL_GATHER, 8, payload, res.fractions())
print(f"8-GPU AllGather 256MB: NCCL {nccl:.1f} GB/s -> FlexLink "
      f"{flex:.1f} GB/s (+{(flex/nccl-1)*100:.0f}%), shares {res.shares}")

# -- 2. data plane: lossless multi-path collective ---------------------------
mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2), ("x", "y"))
x = jnp.arange(4 * 6 * 5, dtype=jnp.float32).reshape(4 * 6, 5)
shares = {"primary": res.shares["nvlink"], "staged": res.shares["pcie"],
          "ortho": res.shares["rdma"]}

flexf = shard_map(lambda v: mp.flex_all_gather(v, "x", shares=shares,
                                               ortho_name="y", tiled=True),
                  mesh=mesh, in_specs=(P("x"),), out_specs=P(),
                  check_vma=False)
reff = shard_map(lambda v: lax.all_gather(v, "x", tiled=True),
                 mesh=mesh, in_specs=(P("x"),), out_specs=P(),
                 check_vma=False)
np.testing.assert_array_equal(np.asarray(jax.jit(flexf)(x)),
                              np.asarray(jax.jit(reff)(x)))
print("multi-path all_gather == single-path reference (bit-exact) -- "
      "lossless, as advertised.")

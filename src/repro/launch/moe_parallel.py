"""shard_map wrapper that turns moe_apply_ep into a drop-in moe_fn."""
from __future__ import annotations

from functools import partial

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.config import ModelConfig
from repro.models.moe import moe_apply_ep
from repro.sharding import batch_axes


def make_ep_moe_fn(mesh: Mesh, capacity_factor: float = 1.25,
                   comm_dtype=None, scatter_down: bool = False):
    """Returns moe_fn(p, x, cfg) -> (y, aux) running expert-parallel.

    Expert weights must be sharded experts->"data", d_ff->"model"
    (``specs_for_schema(..., ep=True)``).  Tokens shard over
    ("pod","data"); the all_to_all runs over "data" within each pod.
    """
    ba = batch_axes(mesh)
    replica = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def moe_fn(p, x, cfg: ModelConfig):
        param_specs = {
            "router": P(None, None),
            "w_gate": P("data", None, "model"),
            "w_up": P("data", None, "model"),
            "w_down": P("data", "model", None),
        }
        if "shared" in p:
            param_specs["shared"] = {
                "w_gate": P(None, "model"),
                "w_up": P(None, "model"),
                "w_down": P("model", None),
            }
            if "b_ff" in p["shared"]:
                param_specs["shared"]["b_ff"] = P("model")
                param_specs["shared"]["b_out"] = P(None)
        bdim = x.shape[0]
        import numpy as np
        from repro.sharding import mesh_axis_sizes
        sizes = mesh_axis_sizes(mesh)
        prod = int(np.prod([sizes[a] for a in ba]))
        x_spec = P(ba if bdim % prod == 0 else None, None, None)

        fn = jax.shard_map(
            partial(_ep_body, cfg=cfg, capacity_factor=capacity_factor,
                    replica=replica, comm_dtype=comm_dtype,
                    scatter_down=scatter_down),
            mesh=mesh,
            in_specs=(param_specs, x_spec),
            out_specs=(x_spec, P()),
            check_vma=False,
        )
        return fn(p, x)

    return moe_fn


def _ep_body(p, x, *, cfg, capacity_factor, replica, comm_dtype=None,
             scatter_down=False):
    return moe_apply_ep(p, x, cfg, data_axis="data", model_axis="model",
                        replica_axes=replica,
                        capacity_factor=capacity_factor,
                        comm_dtype=comm_dtype, scatter_down=scatter_down)

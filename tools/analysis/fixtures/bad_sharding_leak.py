"""Seeded violation: a shard_map output with a sharded out-spec escapes
the stage without a ``PlaneMesh.replicate`` pin — sharding-leak (the
sharding would propagate into the next stage's jit and GSPMD-partition
replicated code).  ``build_stages`` is executed by the sharding pass;
lowering is abstract, so a 1-device mesh suffices."""
from __future__ import annotations


def build_stages():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import plane_contract as pc

    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))

    def attend(x):
        body = jax.shard_map(
            lambda x: x * 2.0,
            check_vma=False, mesh=mesh, in_specs=P("model"),
            out_specs=P("model"))
        return body(x) + 1.0                    # leaks the sharded spec

    args = (jax.ShapeDtypeStruct((8, 16), jnp.float32),)
    return [pc.StageLowering(
        stage="attend[fixture:heads]", fn=attend, args=args,
        rules=pc.sharding_rules("attend", "heads"),
        file=__file__, line=19)]

"""Seeded violation: a psum inside a head-mode attend stage —
collective-not-allowed (the contract says head-mode decode attention is
communication-free; a collective there means pool data is crossing the
mesh).  ``build_stages`` is executed by the sharding pass; lowering is
abstract, so a 1-device mesh suffices."""
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
            lambda x: jax.lax.psum(x, "model"),     # contract: no comm
            check_vma=False, mesh=mesh, in_specs=P(), out_specs=P())
        return body(x)

    args = (jax.ShapeDtypeStruct((8, 16), jnp.float32),)
    return [pc.StageLowering(
        stage="attend[fixture:heads]", fn=attend, args=args,
        rules=pc.sharding_rules("attend", "heads"),
        file=__file__, line=20)]

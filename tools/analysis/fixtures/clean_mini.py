"""A violation-free mini-plane exercising ALL THREE analyzer passes —
the analyzer must return zero findings here.

* ``GoodPlane.step`` follows the staged-decode protocol to the letter;
* ``build``'s jit body only branches on ``is None`` (static);
* ``fns_for`` keys repr(cfg) + plane_mesh.key() and covers both params;
* ``build_stages`` pins its shard_map output replicated before returning.
"""
from __future__ import annotations

_REG = {}


class GoodPlane:
    def step(self, params, fns, host, token_by_req):
        x = fns.embed(params, None)
        for i in range(2):
            sel = fns.select(params, x)
            host.save_new_tokens_fused(i, sel)
            host.access_layer(i)
            host.load_blocks_fused(i, sel)
            host.restore_blocks_fused(i, sel)
            host._drop_pending_evictions(i, protect=(i, sel))
            x = fns.attend(params, x, sel)
        return fns.logits(params, x)


def build(wrap):
    return wrap("select",
                lambda p, x, ctx:
                p["w"] @ x if ctx is None else p["w"] @ (x + ctx))


def fns_for(cfg, plane_mesh):
    key = (repr(cfg), None if plane_mesh is None else plane_mesh.key())
    if key not in _REG:
        _REG[key] = object()
    return _REG[key]


def build_stages():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import plane_contract as pc

    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))

    def attend(x):
        body = jax.shard_map(
            lambda x: x * 2.0,
            check_vma=False, mesh=mesh, in_specs=P("model"),
            out_specs=P("model"))
        rep = NamedSharding(mesh, P())
        return jax.lax.with_sharding_constraint(body(x), rep)

    args = (jax.ShapeDtypeStruct((8, 16), jnp.float32),)
    return [pc.StageLowering(
        stage="attend[fixture:heads]", fn=attend, args=args,
        rules=pc.sharding_rules("attend", "heads"),
        file=__file__, line=51)]

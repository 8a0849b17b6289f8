"""Shared runtime plane assertions, backed by the plane contract.

These read the SAME metadata (``repro.core.plane_contract``) the static
analyzer (tools/analysis) checks, so the launch/trace formulas asserted
at runtime and the ones proven statically can never drift apart.  Before
this module each test file hand-rolled its own copies.
"""
from repro.core import plane_contract as pc
from repro.core.device_pool import block_drop


def assert_cache_hit_invariant(fns):
    """One XLA trace per distinct (stage, shape-signature) bucket — an
    occupancy change or a repeated bucket must be a pure cache hit."""
    assert fns.trace_count == len(fns.shape_signatures), (
        f"trace_count {fns.trace_count} != "
        f"{len(fns.shape_signatures)} shape signatures: "
        f"{sorted(fns.shape_signatures)}")


def staged_launches_per_iteration(cfg) -> int:
    """Jitted launches one staged decode iteration issues (the O(L)
    budget): embed + logits + (select+attend) per attention layer + one
    per recurrent layer."""
    return pc.staged_launches_per_iteration(cfg)


def staged_stage_kinds(cfg) -> int:
    """Distinct stage kinds in the staged pipeline — the per-shape-bucket
    trace budget."""
    return pc.staged_stage_kinds(cfg)


def assert_donation_contract(fns):
    """Every pool-updating stage of a live registry DECLARES buffer
    donation on its pool/cache argument, exactly as the contract's
    ``STAGED_DONATED_STAGES`` table says, and so does the block-drop
    program (``BLOCK_DROP_DONATED``) — on accelerator backends XLA then
    updates the pool in place instead of copying a pool-sized buffer per
    layer per iteration (on CPU the declaration is recorded but not
    armed: CPU buffers are not donatable)."""
    drop = block_drop()
    assert drop.donated == pc.BLOCK_DROP_DONATED, (
        f"block drop declares donate_argnums {drop.donated}, contract "
        f"says {pc.BLOCK_DROP_DONATED}")
    assert drop.donate_active == fns.donate_active, (
        "block drop and stage registry disagree on arming donation")
    for stage, donate in pc.STAGED_DONATED_STAGES.items():
        if stage not in fns.donated:
            continue                     # stage absent for this arch family
        assert fns.donated[stage] == tuple(donate), (
            f"stage {stage!r} declares donate_argnums "
            f"{fns.donated[stage]}, contract says {tuple(donate)}")
    missing = set(pc.STAGED_DONATED_STAGES) - set(fns.donated)
    assert not missing & {"select"}, (
        f"pool-updating stages missing from the registry: {missing}")


def assert_host_sync_invariant(plane, iterations, cfg=None):
    """An async-mode plane's measured per-layer blocking syncs equal the
    contract formula exactly: np.asarray(selected ids) once per attention
    layer per iteration, and nothing else
    (``pc.staged_host_syncs_per_iteration``)."""
    cfg = cfg if cfg is not None else plane.cfg
    expected = pc.staged_host_syncs_per_iteration(cfg) * iterations
    assert plane.host_syncs == expected, (
        f"host_syncs {plane.host_syncs} != {expected} "
        f"({iterations} iterations)")


def assert_stripe_readback_invariant(plane, iterations, rows):
    """The FlashD2H readback stays STRIPE-sized: ``d2h_readback_bytes``
    equals rows x one token's KV per attention layer per iteration — and
    in particular is a vanishing fraction of the pool, pinning that the
    write-back path never copies pool-sized buffers to host."""
    cfg = plane.cfg
    c = plane.state["caches"][plane.pool_layers()[0]]
    itemsize = c["k"].dtype.itemsize
    kv_factor = 2 if "v" in c else 1
    Hkv = c["k"].shape[1]
    D = c["k"].shape[-1]
    stripe = rows * Hkv * D * itemsize * kv_factor
    expected = stripe * len(plane.pool_layers()) * iterations
    assert plane.d2h_readback_bytes == expected, (
        f"d2h_readback_bytes {plane.d2h_readback_bytes} != {expected}")
    assert stripe * len(plane.pool_layers()) < plane.device_bytes(), (
        "per-iteration readback is pool-sized — the write-back path must "
        "move one token's stripe per layer, not the pool")


def assert_mixed_launch_invariant(engine):
    """Contract checks over every MIXED iteration an engine ran, from its
    measured ``mixed_iter_log``:

    * exactly ONE fused FlashD2H per attention layer that had work (and
      none when write-back is off and no prefill group ran there);
    * at most ONE fused FlashH2D per layer;
    * recurrent layers never transfer;
    * measured jitted-launch total == ``mixed_launches_per_iteration``
      (O(L): decode planes x staged budget + prefill groups + finalizes),
      independent of how many rows rode the iteration."""
    assert engine.hybrid is not None, "engine is not running the mixed plane"
    log = engine.mixed_iter_log
    assert log, "no mixed iterations recorded"
    cfg = engine.cfg
    for entry in log:
        for lay, rec in entry["layers"].items():
            if rec["attn"]:
                worked = (rec["decode"] and engine.eng.decode_write_back) \
                    or rec["groups"] > 0
                assert rec["d2h"] == (1 if worked else 0), (lay, rec)
                assert rec["h2d"] <= 1, (lay, rec)
            else:
                assert rec["d2h"] == 0 and rec["h2d"] == 0, (lay, rec)
        expected = pc.mixed_launches_per_iteration(
            cfg, entry["decode_planes"], entry["groups"],
            entry["finalize"])
        assert entry["launches"] == expected, (entry, expected)

"""Pass 3 — sharding-leak detector.

Abstractly lowers every registered stage jit (the raw stage bodies +
abstract call signatures ``StageFns`` records) with ``jax.make_jaxpr``
under its PlaneMesh, then checks the jaxpr against the plane contract's
sharding rules (``plane_contract.sharding_rules``):

* collective-not-allowed — a communication primitive (psum, all_gather,
  ...) appears anywhere in the lowered stage that the contract does not
  list for that (stage, shard mode) — e.g. an accidental gather of the
  sharded pool;
* sharding-leak — a stage OUTPUT that the contract requires replicated
  can carry shard_map out-spec sharding into the caller (no
  ``PlaneMesh.replicate`` pin on the escape path).  The leak taint starts
  at shard_map outputs with non-empty out-specs, is cleared by a
  replicated ``sharding_constraint``, and propagates through every other
  equation; only the contract's ``sharded_out_paths`` (the pool cache a
  select returns) may reach the stage's outputs tainted.

Lowering is ABSTRACT (ShapeDtypeStructs in, jaxpr out): no FLOPs run, so
the whole pass is a few seconds on CPU.  The default target populates the
registries by running two one-token smoke engines (a GQA arch for head
sharding and an MLA arch for block sharding) on a 1-way model mesh —
shard_map over a trivial axis emits the same jaxpr structure as a real
multi-device mesh.
"""
from __future__ import annotations

from pathlib import Path
from typing import List, Sequence, Tuple

from repro.core import plane_contract as pc

from .findings import Finding

# (arch, prompts) for the default registry-populating smoke runs: one GQA
# model (head-mode pool sharding on a 1-way axis) and one MLA model
# (always block mode), so both sharded stage variants get lowered
_DEFAULT_BUILDS: Tuple[Tuple[str, Tuple[int, ...]], ...] = (
    ("qwen2-0.5b", (24, 40)),
    ("minicpm3-4b", (24, 40)),
)


def _rel(repo_root: Path, filename: str) -> str:
    try:
        return str(Path(filename).resolve().relative_to(repo_root.resolve()))
    except ValueError:
        return filename


def _fn_site(fn) -> Tuple[str, int]:
    code = getattr(fn, "__code__", None)
    if code is None:
        return "<unknown>", 0
    return code.co_filename, code.co_firstlineno


def _default_setup(arch):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.models import model as M
    cfg = get_smoke_config(arch)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


def _run_smoke_engine(cfg, params, pm, prompts) -> None:
    import numpy as np
    from repro.serving.engine import EngineConfig, ServingEngine
    from repro.serving.request import Request
    eng = ServingEngine(params, cfg, EngineConfig(
        chunk_size=64, r_max=2, mesh_spec=pm))
    rng = np.random.default_rng(0)
    for p in prompts:
        toks = rng.integers(4, cfg.vocab_size, p).astype(np.int32)
        eng.submit(Request(prompt_len=p, max_new_tokens=2), tokens=toks)
    eng.run()


def _collect_fns(cfg):
    """Registry entries keyed by this cfg (every registry keys repr(cfg)
    first)."""
    from repro.core import device_pool, prefill_plane
    r, out, seen = repr(cfg), [], set()
    for reg in (device_pool._STAGED_FNS, prefill_plane._PREFILL_FNS,
                prefill_plane._ADMIT_EMBED_FNS):
        for key, fns in reg.items():
            k0 = key[0] if isinstance(key, tuple) else key
            if k0 == r and id(fns) not in seen:
                seen.add(id(fns))
                out.append(fns)
    return out


def build_default_stages(get_setup=None) -> List[pc.StageLowering]:
    """Populate the stage registries with smoke workloads and return one
    StageLowering per (registered stage, recorded signature).  get_setup
    lets callers (tests) inject cached (cfg, params) per arch."""
    from repro.launch.plane_mesh import PlaneMesh
    pm = PlaneMesh.resolve(1)
    lowerings: List[pc.StageLowering] = []
    for arch, prompts in _DEFAULT_BUILDS:
        cfg, params = (get_setup or _default_setup)(arch)
        _run_smoke_engine(cfg, params, pm, prompts)
        for fns in _collect_fns(cfg):
            for stage, fn in sorted(fns.raw_fns.items()):
                args = fns.abstract_args.get(stage)
                if args is None:
                    continue            # registered but never launched
                mode = pc.stage_shard_mode(stage, cfg, pm)
                file, line = _fn_site(fn)
                lowerings.append(pc.StageLowering(
                    stage=f"{stage}[{arch}:{mode}]", fn=fn, args=args,
                    rules=pc.sharding_rules(stage, mode),
                    file=file, line=line))
    return lowerings


# -- jaxpr inspection -------------------------------------------------------


def _iter_sub_jaxprs(params: dict):
    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for item in vals:
            if hasattr(item, "eqns"):                   # Jaxpr
                yield item
            elif hasattr(item, "jaxpr"):                # ClosedJaxpr
                yield item.jaxpr


def _collect_collectives(jaxpr, found: set) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in pc.COLLECTIVE_PRIMS:
            found.add(name)
        for sub in _iter_sub_jaxprs(eqn.params):
            _collect_collectives(sub, found)


def _is_replicated_sharding(sharding) -> bool:
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return False
    return all(entry is None for entry in tuple(spec))


def _tainted_outvars(jaxpr) -> set:
    """Indices of jaxpr outvars that can carry shard_map out-spec sharding
    (taint from sharded shard_map outputs, cleared by replicated
    sharding_constraints, propagated through everything else)."""
    tainted = set()

    def _vars(vs):
        return [v for v in vs if not hasattr(v, "val")]   # skip Literals

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "shard_map":
            for var, spec in zip(eqn.outvars, eqn.params["out_specs"]):
                if any(e is not None for e in spec):    # sharded spec
                    tainted.add(var)
        elif name == "sharding_constraint":
            if _is_replicated_sharding(eqn.params.get("sharding")):
                continue                        # explicit replicate: clean
            if any(v in tainted for v in _vars(eqn.invars)):
                tainted.update(eqn.outvars)
        else:
            if any(v in tainted for v in _vars(eqn.invars)):
                tainted.update(eqn.outvars)
    return {i for i, v in enumerate(jaxpr.outvars)
            if not hasattr(v, "val") and v in tainted}


def _out_paths(out_shape) -> List[str]:
    import jax
    leaves_with_paths, _ = jax.tree_util.tree_flatten_with_path(out_shape)
    return [jax.tree_util.keystr(path) for path, _ in leaves_with_paths]


def check_lowering(repo_root: Path, low: pc.StageLowering) -> List[Finding]:
    import jax
    file = _rel(repo_root, low.file)
    try:
        closed, out_shape = jax.make_jaxpr(
            low.fn, return_shape=True)(*low.args)
    except Exception as e:                      # noqa: BLE001 - reported
        return [Finding(
            rule=pc.RULE_SHARDING_LEAK, file=file, line=low.line,
            message=f"[{low.stage}] failed to lower for inspection: "
                    f"{type(e).__name__}: {e}", check="sharding")]
    out: List[Finding] = []
    found: set = set()
    _collect_collectives(closed.jaxpr, found)
    extra = found - low.rules.allowed_collectives
    if extra:
        allowed = (", ".join(sorted(low.rules.allowed_collectives))
                   or "none")
        out.append(Finding(
            rule=pc.RULE_COLLECTIVE, file=file, line=low.line,
            message=f"[{low.stage}] collective(s) "
                    f"{', '.join(sorted(extra))} in the lowered stage; "
                    f"contract allows: {allowed}", check="sharding"))
    paths = _out_paths(out_shape)
    for i in _tainted_outvars(closed.jaxpr):
        path = (paths[i] if i < len(paths) else f"<leaf {i}>") or "<root>"
        if any(tok in path for tok in low.rules.sharded_out_paths):
            continue                            # sharded by contract
        out.append(Finding(
            rule=pc.RULE_SHARDING_LEAK, file=file, line=low.line,
            message=f"[{low.stage}] output {path} can carry shard_map "
                    f"sharding into replicated callers — pin it with "
                    f"PlaneMesh.replicate", check="sharding"))
    return out


def _resolve_builder(repo_root: Path, spec: str):
    """'path/to/file.py:function' -> the build_stages callable."""
    import importlib.util
    file, _, func = spec.partition(":")
    path = repo_root / file
    mod_spec = importlib.util.spec_from_file_location(
        "plane_analysis_fixture", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return getattr(mod, func)


def run(repo_root: Path, target: pc.AnalysisTarget,
        get_setup=None) -> List[Finding]:
    if target.sharding is None:
        return []
    if target.sharding == "default":
        lowerings: Sequence[pc.StageLowering] = \
            build_default_stages(get_setup)
    else:
        lowerings = _resolve_builder(repo_root, target.sharding)()
    out: List[Finding] = []
    for low in lowerings:
        out.extend(check_lowering(repo_root, low))
    return out
